"""Overlap-aware shuffle (DESIGN.md §3a): local/remote edge-half invariants,
split-aggregation numerics vs the blocking baseline, the chunked exchange,
the wire format, and serial == pipelined determinism under overlap."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from dataclasses import replace

from repro.core import (
    build_split_plan,
    partition_graph,
    presample,
    sim_shuffle,
)
from repro.core.shuffle import chunk_slices, sim_alltoall, wire_cast
from repro.core.splitting import repad_plan
from repro.graph.datasets import make_dataset
from repro.graph.sampling import sample_minibatch
from repro.models.gnn import GNNSpec, init_gnn_params
from repro.models.gnn.layers import gnn_forward
from repro.train.plan_io import load_features, plan_to_device
from repro.train.trainer import TrainConfig, Trainer, modeled_wire_bytes

NDEV = 4


@pytest.fixture(scope="module")
def ds():
    return make_dataset("tiny")


@pytest.fixture(scope="module")
def part(ds):
    w = presample(ds.graph, ds.train_ids, [3, 3], 16, num_epochs=1)
    return partition_graph(ds.graph, NDEV, method="gsplit", weights=w)


def _plan(ds, part, n_targets=16, seed=0):
    rng = np.random.default_rng(seed)
    mb = sample_minibatch(ds.graph, ds.train_ids[:n_targets], [3, 3], rng)
    # halves are opt-in end to end: blocking callers never build them
    return build_split_plan(mb, part.assignment, NDEV, with_halves=True)


def _check_halves(plan):
    """The edge-half partition invariant: every valid edge in exactly one
    half, sources in the half's coordinate space, and the full mixed-buffer
    coordinate reconstructible from the half coordinate."""
    for lp in plan.layers:
        P, S = lp.edge_src.shape[0], lp.send_idx.shape[-1]
        for p in range(P):
            valid = np.flatnonzero(lp.edge_mask[p])
            lids = lp.ledge_ids[p][lp.ledge_mask[p]]
            rids = lp.redge_ids[p][lp.redge_mask[p]]
            both = np.concatenate([lids, rids])
            # disjoint cover of exactly the valid edge slots
            assert len(set(both)) == both.size, "halves overlap"
            assert set(both) == set(valid), "halves miss/invent edges"
            # local sources index the local block; dst rows match
            lsrc = lp.ledge_src[p][lp.ledge_mask[p]]
            assert (lsrc < lp.n_local).all()
            np.testing.assert_array_equal(lsrc, lp.edge_src[p][lids])
            np.testing.assert_array_equal(
                lp.ledge_dst[p][lp.ledge_mask[p]], lp.edge_dst[p][lids]
            )
            # remote sources are recv-region relative: n_local + r == full
            rsrc = lp.redge_src[p][lp.redge_mask[p]]
            assert rsrc.size == 0 or (
                (rsrc >= 0) & (rsrc < P * S)
            ).all(), "remote src outside the recv region"
            np.testing.assert_array_equal(
                rsrc + lp.n_local, lp.edge_src[p][rids]
            )
            np.testing.assert_array_equal(
                lp.redge_dst[p][lp.redge_mask[p]], lp.edge_dst[p][rids]
            )


def test_halves_partition_every_edge(ds, part):
    plan = _plan(ds, part)
    assert any(lp.redge_mask.any() for lp in plan.layers), (
        "fixture has no cross-split edges — the test would be vacuous"
    )
    _check_halves(plan)


def test_halves_survive_repad_growth(ds, part):
    """Repadding to high-water marks raised by a *larger* batch grows the
    local region, the send width, and every half axis; the partition
    invariant (and the n_local + redge_src reconstruction) must survive."""
    small = _plan(ds, part, n_targets=12, seed=1)
    big = _plan(ds, part, n_targets=48, seed=2)
    hwm: dict = {}
    repad_plan(big, hwm)
    grew = repad_plan(small, hwm)
    for lp, lp_big in zip(grew.layers, big.layers):
        assert lp.edge_src.shape == lp_big.edge_src.shape
        assert lp.ledge_src.shape == lp_big.ledge_src.shape
        assert lp.redge_src.shape == lp_big.redge_src.shape
        assert lp.lpack_perm.shape == lp_big.lpack_perm.shape
        assert lp.rpack_perm.shape == lp_big.rpack_perm.shape
    _check_halves(grew)
    _check_halves(big)


def test_chunk_slices_tile_exactly():
    for width, chunks, align in [(13, 1, 1), (13, 4, 1), (64, 4, 8),
                                 (24, 3, 8), (8, 16, 8), (40, 3, 1)]:
        sls = chunk_slices(width, chunks, align)
        cover = []
        for sl in sls:
            assert sl.start % align == 0
            assert sl.stop == width or sl.stop % align == 0
            cover.extend(range(sl.start, sl.stop))
        assert cover == list(range(width)), (width, chunks, align, sls)
        assert len(sls) <= max(chunks, 1)


def test_wire_cast_contract():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 8)), jnp.float32)
    w, restore = wire_cast(x, "bfloat16")
    assert w.dtype == jnp.bfloat16 and restore == jnp.float32
    # fp32 wire is the identity; integer payloads are never quantized
    w32, _ = wire_cast(x, "float32")
    assert w32 is x
    ids = jnp.arange(12, dtype=jnp.int32)
    wi, _ = wire_cast(ids, "bfloat16")
    assert wi.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(sim_alltoall(ids.reshape(2, 2, 3), "float16")),
        np.asarray(sim_alltoall(ids.reshape(2, 2, 3))),
    )
    with pytest.raises(ValueError):
        wire_cast(x, "int8")


@pytest.mark.parametrize("model", ["sage", "gcn", "gat"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_overlap_matches_blocking_baseline(ds, part, model, backend):
    """Split local/remote aggregation + chunked exchange == the blocking
    shuffle -> aggregate within fp tolerance (the partial sums reassociate
    the per-destination reduction), for fresh and repadded plans."""
    plan = _plan(ds, part)
    big = _plan(ds, part, n_targets=48, seed=3)
    hwm: dict = {}
    repad_plan(big, hwm)
    repad_plan(plan, hwm)  # plan now carries grown/rebased layouts
    pa = plan_to_device(plan, with_halves=True)
    feats = jnp.asarray(load_features(plan, ds.features))
    spec = GNNSpec(
        model=model, in_dim=ds.spec.feat_dim, hidden_dim=16, out_dim=4,
        num_layers=2, num_heads=2,
    )
    params = init_gnn_params(jax.random.PRNGKey(0), spec)
    ref = gnn_forward(spec, params, feats, pa, sim_shuffle)
    for chunks in (1, 3):
        ovl = replace(
            spec, overlap=True, shuffle_chunks=chunks, agg_backend=backend,
        )
        got = gnn_forward(ovl, params, feats, pa, sim_shuffle)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=5e-5, atol=5e-5
        )
    # bf16 wire: only the shuffled rows are quantized (documented tolerance)
    bf = replace(spec, overlap=True, shuffle_chunks=2, agg_backend=backend,
                 wire_dtype="bfloat16")
    got = gnn_forward(bf, params, feats, pa, sim_shuffle)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=5e-2, atol=5e-2
    )


def test_overlap_gradients_match_baseline(ds, part):
    plan = _plan(ds, part)
    pa = plan_to_device(plan, with_halves=True)
    feats = jnp.asarray(load_features(plan, ds.features))
    spec = GNNSpec(model="sage", in_dim=ds.spec.feat_dim, hidden_dim=16,
                   out_dim=4, num_layers=2)
    ovl = replace(spec, overlap=True, shuffle_chunks=2)
    params = init_gnn_params(jax.random.PRNGKey(0), spec)

    def loss(p, s):
        return (gnn_forward(s, p, feats, pa, sim_shuffle) ** 2).sum()

    g_ref = jax.grad(loss)(params, spec)
    g_ovl = jax.grad(loss)(params, ovl)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_ovl), jax.tree_util.tree_leaves(g_ref)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4
        )


def _trajectory(ds, source, epochs=2, iters=3, **kw):
    cfg = TrainConfig(
        mode="split", num_devices=NDEV, fanouts=(4, 4), batch_size=32,
        presample_epochs=2, plan_source=source, pipeline_depth=3,
        plan_workers=2, seed=7, **kw,
    )
    tr = Trainer(ds, GNNSpec(
        model="sage", in_dim=ds.spec.feat_dim, hidden_dim=16,
        out_dim=ds.spec.num_classes, num_layers=2,
    ), cfg)
    traj = []
    for _ in range(epochs):
        st = tr.train_epoch(max_iters=iters)
        traj += [(i.loss, i.accuracy) for i in st.iters]
    return tr, traj


def test_overlap_fp32_serial_equals_pipelined_bitwise(ds):
    """The §6 determinism contract extends to the overlap schedule: with an
    fp32 wire the overlapped epoch walks bit-identical losses on serial and
    pipelined delivery (same padded shapes, same traced program)."""
    _, serial = _trajectory(ds, "serial", shuffle_overlap=True,
                            shuffle_chunks=2)
    _, piped = _trajectory(ds, "pipelined", shuffle_overlap=True,
                           shuffle_chunks=2)
    assert len(serial) == len(piped) > 0
    assert serial == piped


def test_overlap_tracks_blocking_trainer(ds):
    """Overlapped and blocking trainers walk fp-tolerance-close trajectories
    (not bitwise — split aggregation reassociates sums), and bf16 wire stays
    finite and close on this scale."""
    _, base = _trajectory(ds, "serial")
    _, ovl = _trajectory(ds, "serial", shuffle_overlap=True,
                         shuffle_chunks=2)
    np.testing.assert_allclose(
        [x[0] for x in ovl], [x[0] for x in base], rtol=2e-4
    )
    _, bf = _trajectory(ds, "serial", shuffle_overlap=True, shuffle_chunks=2,
                        wire_dtype="bfloat16")
    assert np.isfinite([x[0] for x in bf]).all()
    np.testing.assert_allclose(
        [x[0] for x in bf], [x[0] for x in base], rtol=0.2
    )


def test_dp_mode_overlap_is_exact(ds):
    """dp plans are all-local: the remote half is empty, the local half is
    the full edge set in the same order, so overlap == blocking bitwise."""
    cfgs = [dict(), dict(shuffle_overlap=True)]
    outs = []
    for kw in cfgs:
        cfg = TrainConfig(mode="dp", num_devices=NDEV, fanouts=(4, 4),
                          batch_size=32, presample_epochs=2,
                          plan_source="serial", seed=7, **kw)
        tr = Trainer(ds, GNNSpec(
            model="sage", in_dim=ds.spec.feat_dim, hidden_dim=16,
            out_dim=ds.spec.num_classes, num_layers=2,
        ), cfg)
        st = tr.train_epoch(max_iters=3)
        outs.append([(i.loss, i.accuracy) for i in st.iters])
    assert outs[0] == outs[1]


def test_wire_bytes_model_halves_under_bf16(ds, part):
    plan = _plan(ds, part)
    spec = GNNSpec(model="sage", in_dim=ds.spec.feat_dim, hidden_dim=16,
                   out_dim=4, num_layers=2)
    b32 = modeled_wire_bytes(plan, spec, "float32")
    b16 = modeled_wire_bytes(plan, spec, "bfloat16")
    assert b32 > 0 and b32 == 2 * b16


def test_wire_bytes_gat_overlap_counts_the_exchanged_heads(ds, part):
    """The overlapped GAT path ships each layer's transformed rows, all its
    heads, plus H a_src scores: a hidden layer's heads are concatenated
    (``d_out`` wide), the output layer's are averaged only after the
    aggregation (``H * out_dim`` wide on the wire)."""
    plan = _plan(ds, part)
    spec = GNNSpec(model="gat", in_dim=ds.spec.feat_dim, hidden_dim=16,
                   out_dim=4, num_layers=2, num_heads=2, overlap=True)
    params = init_gnn_params(jax.random.PRNGKey(0), spec)
    exchanged = [p["w"].shape[1] * p["w"].shape[2] + 2 for p in params]
    assert exchanged == [16 + 2, 2 * 4 + 2]
    rows = [lp.shuffle_rows() for lp in plan.layers]  # dst depth 0 = output
    assert rows[0] > 0 and rows[1] > 0
    assert modeled_wire_bytes(plan, spec, "float32") == 4 * (
        rows[1] * exchanged[0] + rows[0] * exchanged[1])


def test_signature_keys_on_overlap_knobs(ds, part):
    from repro.runtime.signature import plan_signature

    plan = _plan(ds, part)
    s1 = plan_signature(plan, extra=("float32", 1, False))
    s2 = plan_signature(plan, extra=("bfloat16", 4, True))
    assert s1 != s2
    assert s1 == plan_signature(plan, extra=("float32", 1, False))


# --------------------------------------------------------------------------- #
# property-based sweep (skips cleanly without hypothesis)
# --------------------------------------------------------------------------- #
from repro.testing import given, settings, st  # hypothesis or fallback

HAVE_HYPOTHESIS = True  # repro.testing provides a deterministic fallback

if HAVE_HYPOTHESIS:

    @settings(deadline=None, max_examples=10)
    @given(
        E=st.integers(min_value=1, max_value=300),
        N=st.integers(min_value=1, max_value=64),
        S=st.integers(min_value=0, max_value=16),
        grow_n=st.integers(min_value=0, max_value=32),
        grow_s=st.integers(min_value=0, max_value=8),
        seed=st.integers(min_value=0, max_value=64),
    )
    def test_halves_property(E, N, S, grow_n, grow_s, seed):
        """split_edge_halves covers every valid edge exactly once, halves
        stay disjoint, and the recv-relative remote encoding reconstructs
        the mixed-buffer coordinate — including after simulated HWM growth
        of the local region and the send width (the repad rebase)."""
        from repro.core.splitting import pad_axis, split_edge_halves

        rng = np.random.default_rng(seed)
        P = 2
        num_out = 8
        M = N + P * S  # mixed-buffer width
        edge_src = rng.integers(0, M, size=(P, E)).astype(np.int32)
        edge_dst = rng.integers(0, num_out, size=(P, E)).astype(np.int32)
        edge_mask = rng.random((P, E)) > 0.3
        halves = split_edge_halves(
            edge_src, edge_dst, edge_mask, N, num_out, pad_multiple=8
        )
        for p in range(P):
            valid = np.flatnonzero(edge_mask[p])
            lids = halves["ledge_ids"][p][halves["ledge_mask"][p]]
            rids = halves["redge_ids"][p][halves["redge_mask"][p]]
            both = np.concatenate([lids, rids])
            assert len(set(both)) == both.size
            assert set(both) == set(valid)
            lsrc = halves["ledge_src"][p][halves["ledge_mask"][p]]
            rsrc = halves["redge_src"][p][halves["redge_mask"][p]]
            assert (lsrc < N).all()
            np.testing.assert_array_equal(lsrc, edge_src[p][lids])
            np.testing.assert_array_equal(rsrc + N, edge_src[p][rids])

        # simulated repad: grow the local region and the send width, apply
        # the same rebases repad_plan performs, re-check reconstruction
        N2, S2 = N + grow_n, S + grow_s if S else S
        full = edge_src.copy()
        if S > 0 and (N2 != N or S2 != S):
            remote = full >= N
            q, slot = np.divmod(full[remote].astype(np.int64) - N, S)
            full[remote] = (N2 + q * S2 + slot).astype(np.int32)
        rsrc_all = halves["redge_src"]
        if S > 0 and S2 != S:
            q, slot = np.divmod(rsrc_all.astype(np.int64), S)
            rsrc_all = (q * S2 + slot).astype(np.int32)
        rsrc_all = pad_axis(rsrc_all, 1, rsrc_all.shape[1] + 4)
        rmask = pad_axis(halves["redge_mask"], 1, rsrc_all.shape[1])
        rids_a = pad_axis(halves["redge_ids"], 1, rsrc_all.shape[1])
        for p in range(P):
            rs = rsrc_all[p][rmask[p]]
            np.testing.assert_array_equal(rs + N2, full[p][rids_a[p][rmask[p]]])
