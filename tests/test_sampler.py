"""Device-resident cooperative sampling engine (docs/SAMPLER.md).

Covers the shard format, the wavefront kernel (Pallas == jnp oracle and the
host-sampler semantics), RNG uniformity (chi-square), the static-cap frontier
utilities, device-built plan validity, determinism / cap-independence, the
overflow -> host fallback, end-to-end training in ``"device"`` mode for all
three GNN models, the device serial == pipelined contract, and spmd == sim
for the per-shard loop (subprocess, 4 devices).
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_split_plan, partition_graph, presample
from repro.graph.datasets import make_dataset
from repro.graph.sampling import NeighborSampler
from repro.models.gnn import GNNSpec
from repro.sampler import DeviceSampler, build_shards
from repro.sampler.frontier import bucket_by_owner, sorted_unique_capped
from repro.sampler.ops import wavefront_expand
from repro.sampler.ref import INVALID, SELF_LOOP, wavefront_expand_ref
from repro.sampler.rng import draw_u32, fold_key_pair
from repro.train.trainer import TrainConfig, Trainer

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
FANOUTS = [4, 3]
NDEV = 4
BATCH = 32


@pytest.fixture(scope="module")
def ds():
    return make_dataset("tiny")


@pytest.fixture(scope="module")
def setup(ds):
    host = NeighborSampler(ds.graph, ds.train_ids, FANOUTS, BATCH, seed=7)
    w = presample(ds.graph, ds.train_ids, FANOUTS, BATCH, num_epochs=1)
    part = partition_graph(ds.graph, NDEV, method="gsplit", weights=w)
    eng = DeviceSampler(
        ds.graph, part.assignment, NDEV, FANOUTS, 7, host, backend="jnp"
    )
    return host, part, eng


# --------------------------------------------------------------------- #
# shard format
# --------------------------------------------------------------------- #
def test_shard_reconstructs_csr_rows(ds, setup):
    _, part, _ = setup
    shards = build_shards(ds.graph, part.assignment, NDEV)
    shards.validate()
    rng = np.random.default_rng(0)
    for v in rng.choice(ds.graph.num_nodes, size=64, replace=False):
        p = shards.owner[v]
        r = shards.local_row[v]
        s, e = shards.indptr[p, r], shards.indptr[p, r + 1]
        np.testing.assert_array_equal(
            shards.indices[p, s:e], ds.graph.neighbors(v)
        )
        # edge ids point back into the global CSR slice of v
        np.testing.assert_array_equal(
            shards.edge_id[p, s:e],
            np.arange(ds.graph.indptr[v], ds.graph.indptr[v + 1]),
        )


# --------------------------------------------------------------------- #
# wavefront kernel
# --------------------------------------------------------------------- #
def _toy_block(graph, rng, n=96):
    vids = rng.choice(graph.num_nodes, size=n).astype(np.int32)
    deg = np.diff(graph.indptr)[vids].astype(np.int32)
    deg[:5] = -1  # invalid rows
    return jnp.asarray(vids), jnp.asarray(deg), deg


def test_kernel_matches_jnp_oracle(ds):
    rng = np.random.default_rng(1)
    vids, deg, _ = _toy_block(ds.graph, rng)
    key = jnp.asarray(fold_key_pair(7, 0, 0), jnp.uint32)
    for fanout in (3, 8):
        got = wavefront_expand(
            vids, deg, key, fanout, backend="pallas", interpret=True
        )
        ref = wavefront_expand_ref(vids, deg, key, fanout)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_expand_semantics_match_host_sampler(ds):
    """Take-all / sampled / self-loop / invalid semantics of the codes."""
    rng = np.random.default_rng(2)
    vids, degj, deg = _toy_block(ds.graph, rng)
    fanout = 4
    codes = np.asarray(
        wavefront_expand(
            vids, degj, jnp.asarray([123, 456], jnp.uint32), fanout,
            backend="jnp", interpret=True,
        )
    )
    for i in range(len(deg)):
        c = codes[i]
        if deg[i] < 0:
            assert np.all(c == INVALID)
        elif deg[i] == 0:
            assert c[0] == SELF_LOOP and np.all(c[1:] == INVALID)
        elif deg[i] <= fanout:
            np.testing.assert_array_equal(c[: deg[i]], np.arange(deg[i]))
            assert np.all(c[deg[i] :] == INVALID)
        else:
            valid = c[c != INVALID]
            assert valid.size >= 1
            assert np.all((valid >= 0) & (valid < deg[i]))
            assert len(np.unique(valid)) == len(valid)  # dedup'd draws


def test_chi_square_uniform_draws(ds):
    """Counter-based draws are uniform over the degree (host semantics)."""
    deg = int(np.diff(ds.graph.indptr).max())
    assert deg > 8
    v = int(np.argmax(np.diff(ds.graph.indptr)))
    T, fanout = 4000, 4
    keys = np.array(
        [fold_key_pair(7, 0x5A3D, 0, t, 0) for t in range(T)], np.uint32
    )  # (T, 2)
    u = np.asarray(
        draw_u32(
            jnp.uint32(v),
            jnp.arange(fanout, dtype=jnp.uint32)[None, :],
            jnp.asarray(keys[:, 0])[:, None],
            jnp.asarray(keys[:, 1])[:, None],
        )
    )
    offs = u % deg
    counts = np.bincount(offs.reshape(-1), minlength=deg)
    total = counts.sum()
    expected = total / deg
    chi2 = float(((counts - expected) ** 2 / expected).sum())
    df = deg - 1
    # chi2 ~ N(df, sqrt(2 df)) for large df; 5 sigma keeps this deterministic
    # test far from flaking while catching any real non-uniformity
    assert chi2 < df + 5.0 * np.sqrt(2.0 * df), (chi2, df)


def test_chi_square_end_to_end_edge_frequencies(ds, setup):
    """Post-dedup edge-selection frequencies from the engine are uniform
    across a hot vertex's in-edges — the observable the host sampler's
    uniform-with-replacement semantics predicts."""
    host, part, _ = setup
    deg_all = np.diff(ds.graph.indptr)
    v = int(np.argmax(deg_all))
    d = int(deg_all[v])
    eng = DeviceSampler(
        ds.graph, part.assignment, NDEV, [4], 7, host, backend="jnp"
    )
    targets = np.array([v], np.int64)
    counts = np.zeros(d, np.int64)
    for t in range(300):
        mb = eng.sample_batch(targets, 0, t)
        lay = mb.layers[0]
        eids = lay.edge_id[lay.dst == v]
        counts += np.bincount(eids - ds.graph.indptr[v], minlength=d)
    total = counts.sum()
    expected = total / d
    chi2 = float(((counts - expected) ** 2 / expected).sum())
    df = d - 1
    assert chi2 < df + 5.0 * np.sqrt(2.0 * df), (chi2, df)


# --------------------------------------------------------------------- #
# static-cap frontier utilities
# --------------------------------------------------------------------- #
def test_sorted_unique_capped_matches_numpy():
    rng = np.random.default_rng(3)
    vals = rng.integers(0, 50, size=200).astype(np.int32)
    valid = rng.random(200) > 0.3
    want = np.unique(vals[valid])
    out, cnt, over = sorted_unique_capped(
        jnp.asarray(vals), jnp.asarray(valid), 64, 50
    )
    assert not bool(over) and int(cnt) == want.size
    np.testing.assert_array_equal(np.asarray(out)[: want.size], want)
    # overflow: cap below the unique count flags and truncates to the prefix
    out2, cnt2, over2 = sorted_unique_capped(
        jnp.asarray(vals), jnp.asarray(valid), 8, 50
    )
    assert bool(over2) and int(cnt2) == 8
    np.testing.assert_array_equal(np.asarray(out2), want[:8])


def test_bucket_by_owner_matches_numpy():
    rng = np.random.default_rng(4)
    V, P, cap = 40, 3, 16
    owner = rng.integers(0, P, size=V).astype(np.int32)
    vals = rng.integers(0, V, size=120).astype(np.int32)
    valid = rng.random(120) > 0.2
    buf, cnt, over = bucket_by_owner(
        jnp.asarray(vals), jnp.asarray(valid), jnp.asarray(owner), P, cap, V
    )
    assert not bool(over)
    u = np.unique(vals[valid])
    for q in range(P):
        want = u[owner[u] == q]
        assert int(cnt[q]) == want.size
        np.testing.assert_array_equal(np.asarray(buf)[q, : want.size], want)


# --------------------------------------------------------------------- #
# device-built plans
# --------------------------------------------------------------------- #
def test_device_plan_validity_invariants(ds, setup):
    host, part, eng = setup
    targets = host.epoch_targets(0)[0]
    mb = eng.sample_batch(targets, 0, 0)
    L = len(FANOUTS)
    assert np.array_equal(mb.frontiers[0], np.unique(targets))
    deg = np.diff(ds.graph.indptr)
    for i in range(L):
        lay = mb.layers[i]
        # frontier nesting + closure over sampled sources
        np.testing.assert_array_equal(
            mb.frontiers[i + 1],
            np.unique(np.concatenate([mb.frontiers[i], lay.src])),
        )
        # no duplicate edges per destination; self-loops only at degree 0
        key = lay.dst * (ds.graph.num_edges + 2) + (lay.edge_id + 1)
        assert len(np.unique(key)) == len(key)
        assert np.all(deg[lay.dst[lay.edge_id == -1]] == 0)

    plan = build_split_plan(mb, part.assignment, NDEV)
    for d in range(L + 1):
        ids, mask = plan.front_ids[d], plan.node_mask[d]
        # ownership: every masked row sits on its f_G device
        for p in range(NDEV):
            assert np.all(part.assignment[ids[p][mask[p]]] == p)
        assert mask.sum() == mb.frontiers[d].size
    for i, lp in enumerate(plan.layers):
        # self_pos: each depth-i vertex's row at depth i+1 holds the same id
        ids_i, ids_j = plan.front_ids[i], plan.front_ids[i + 1]
        for p in range(NDEV):
            m = plan.node_mask[i][p]
            np.testing.assert_array_equal(
                ids_j[p][lp.self_pos[p][m]], ids_i[p][m]
            )
        # dst-sorted layout contract (DESIGN.md §3)
        E = lp.edge_src.shape[1]
        for p in range(NDEV):
            assert np.array_equal(np.sort(lp.edge_perm[p]), np.arange(E))
            counts = np.bincount(
                lp.edge_dst[p][lp.edge_mask[p]],
                minlength=plan.front_ids[i].shape[1],
            )
            np.testing.assert_array_equal(np.diff(lp.seg_offsets[p]), counts)


def test_determinism_and_cap_independence(ds, setup):
    host, part, eng = setup
    targets = host.epoch_targets(0)[0]
    a = eng.sample_batch(targets, 3, 1)
    b = eng.sample_batch(targets, 3, 1)
    for la, lb in zip(a.layers, b.layers):
        np.testing.assert_array_equal(la.src, lb.src)
        np.testing.assert_array_equal(la.edge_id, lb.edge_id)
    # bigger caps change shapes, never content (draws key on vertex ids)
    big = DeviceSampler(
        ds.graph, part.assignment, NDEV, FANOUTS, 7, host,
        backend="jnp", headroom=4.0,
    )
    c = big.sample_batch(targets, 3, 1)
    for la, lc in zip(a.layers, c.layers):
        np.testing.assert_array_equal(la.src, lc.src)
        np.testing.assert_array_equal(la.edge_id, lc.edge_id)
    for fa, fc in zip(a.frontiers, c.frontiers):
        np.testing.assert_array_equal(fa, fc)
    # a different epoch draws a different sample
    d = eng.sample_batch(targets, 4, 1)
    assert any(
        la.src.shape != ld.src.shape or not np.array_equal(la.src, ld.src)
        for la, ld in zip(a.layers, d.layers)
    )


def test_overflow_falls_back_to_host_sampler(ds, setup):
    host, part, _ = setup
    eng = DeviceSampler(
        ds.graph, part.assignment, NDEV, FANOUTS, 7, host, backend="jnp"
    )
    eng._caps["N1"] = 16  # force an overflow on a real batch
    targets = host.epoch_targets(0)[0]
    mb = eng.sample_batch(targets, 0, 0)
    want = host.sample_batch(targets, 0, 0)
    assert eng.fallbacks == 1  # documented fallback, not silent truncation
    for a, b in zip(mb.layers, want.layers):
        np.testing.assert_array_equal(a.src, b.src)
        np.testing.assert_array_equal(a.dst, b.dst)
        np.testing.assert_array_equal(a.edge_id, b.edge_id)
    for fa, fb in zip(mb.frontiers, want.frontiers):
        np.testing.assert_array_equal(fa, fb)
    # the flagged cap doubles at the epoch boundary and stops overflowing
    eng.refresh_caps()
    assert eng._caps["N1"] >= 32
    eng.sample_batch(targets, 0, 0)
    assert eng.fallbacks == 1


# --------------------------------------------------------------------- #
# trainer integration ("device" plan source)
# --------------------------------------------------------------------- #
def _traj(ds, source, model="sage", backend="jnp", epochs=2, iters=3):
    spec = GNNSpec(
        model=model, in_dim=ds.spec.feat_dim, hidden_dim=16,
        out_dim=ds.spec.num_classes, num_layers=2, num_heads=4,
    )
    cfg = TrainConfig(
        mode="split", num_devices=NDEV, fanouts=tuple(FANOUTS),
        batch_size=BATCH, presample_epochs=2, plan_source=source,
        plan_workers=2, sampler_backend=backend, seed=7,
    )
    tr = Trainer(ds, spec, cfg)
    out = []
    for _ in range(epochs):
        st = tr.train_epoch(max_iters=iters)
        out += [(i.loss, i.accuracy) for i in st.iters]
    return tr, out, st


@pytest.mark.parametrize("model", ["sage", "gcn", "gat"])
def test_device_mode_trains_all_models(ds, model):
    _, traj, last = _traj(ds, "device", model=model, epochs=1)
    assert len(traj) > 0
    assert all(np.isfinite(l) for l, _ in traj)
    assert last.pipeline["sampler_fallbacks"] <= last.pipeline["sampler_batches"]


def test_device_serial_matches_device_pipelined(ds):
    _, serial, _ = _traj(ds, "device")
    _, pipelined, last = _traj(ds, "device_pipelined")
    assert serial == pipelined  # bit-for-bit (keyed draws + frozen caps)
    assert last.pipeline["delivered"] > 0
    assert "sampler_caps" in last.pipeline


def test_device_mode_requires_split(ds):
    spec = GNNSpec(model="sage", in_dim=ds.spec.feat_dim, hidden_dim=16,
                   out_dim=ds.spec.num_classes, num_layers=2)
    with pytest.raises(ValueError, match="device"):
        Trainer(ds, spec, TrainConfig(mode="dp", plan_source="device",
                                      fanouts=(4, 4), batch_size=BATCH,
                                      presample_epochs=1))


# --------------------------------------------------------------------- #
# presample accumulation (bincount fast path)
# --------------------------------------------------------------------- #
def test_presample_accumulate_matches_add_at(ds, setup):
    host, _, _ = setup
    from repro.core.presample import _accumulate

    mbs = [
        host.sample_batch(t, 0, i)
        for i, t in enumerate(host.epoch_targets(0))
    ]
    k_v = np.zeros(ds.graph.num_nodes, np.int64)
    k_e = np.zeros(ds.graph.num_edges, np.int64)
    _accumulate(k_v, k_e, iter(mbs))  # generator input must stream fine
    rv = np.zeros_like(k_v)
    re = np.zeros_like(k_e)
    for mb in mbs:
        for frontier in mb.frontiers[:-1]:
            np.add.at(rv, frontier, 1)
        for layer in mb.layers:
            np.add.at(re, layer.edge_id[layer.edge_id >= 0], 1)
    np.testing.assert_array_equal(k_v, rv)
    np.testing.assert_array_equal(k_e, re)


# --------------------------------------------------------------------- #
# spmd: the per-shard loop under shard_map == sim mode
# --------------------------------------------------------------------- #
def test_spmd_sampling_matches_sim_and_trains():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.graph.datasets import make_dataset
        from repro.graph.sampling import NeighborSampler
        from repro.core import presample, partition_graph, build_split_plan, sim_shuffle
        from repro.launch.sharding import make_split_mesh, sampler_shard_specs
        from repro.models.gnn import GNNSpec, init_gnn_params
        from repro.models.gnn.layers import gnn_forward, gnn_forward_split_mesh
        from repro.sampler import DeviceSampler, sample_minibatch_spmd
        from repro.sampler.engine import _sample_device
        from repro.train.plan_io import plan_to_device, load_features

        NDEV, FANOUTS = 4, (4, 3)
        ds = make_dataset("tiny")
        host = NeighborSampler(ds.graph, ds.train_ids, list(FANOUTS), 32, seed=7)
        w = presample(ds.graph, ds.train_ids, list(FANOUTS), 32, num_epochs=1)
        part = partition_graph(ds.graph, NDEV, method="gsplit", weights=w)
        eng = DeviceSampler(ds.graph, part.assignment, NDEV, list(FANOUTS), 7,
                            host, backend="jnp")
        targets = host.epoch_targets(0)[0]
        tpad = np.zeros(32, np.int32); tpad[:len(targets)] = targets
        keys = jnp.asarray(eng.layer_keys(0, 0))
        caps = eng.caps_tuple()

        ref = _sample_device(eng._dev, jnp.asarray(tpad),
                             jnp.int32(len(targets)), keys, caps=caps,
                             fanouts=FANOUTS, backend="jnp", interpret=True)

        mesh = make_split_mesh(1, NDEV)
        specs = sampler_shard_specs(eng._dev, split_axis="split")
        def body(dev):
            dev_local = {k: (v[0] if specs[k][0] == "split" else v)
                         for k, v in dev.items()}
            fronts, counts, layers, flags = sample_minibatch_spmd(
                dev_local, jnp.asarray(tpad), jnp.int32(len(targets)), keys,
                caps=caps, fanouts=FANOUTS, axis_name="split",
                num_parts=NDEV, backend="jnp")
            return ([f[None] for f in fronts], [c[None] for c in counts],
                    [{k: v[None] for k, v in l.items()} for l in layers],
                    {k: v[None] for k, v in flags.items()})
        flag_keys = ("N0", "N1", "N2", "C0", "C1", "X0", "X1")
        out_specs = ([P("split")] * 3, [P("split")] * 3,
                     [{k: P("split") for k in ("dst", "src", "eid", "valid")}
                      for _ in FANOUTS],
                     {k: P("split") for k in flag_keys})
        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(specs,),
                                   out_specs=out_specs, check_vma=False))
        got = fn(eng._dev)
        for d in range(3):
            np.testing.assert_array_equal(np.asarray(got[0][d]),
                                          np.asarray(ref[0][d]))
            np.testing.assert_array_equal(np.asarray(got[1][d]),
                                          np.asarray(ref[1][d]))
        for l in range(2):
            for k in ("dst", "src", "eid", "valid"):
                np.testing.assert_array_equal(np.asarray(got[2][l][k]),
                                              np.asarray(ref[2][l][k]))
        # per-shard overflow flags: none set, and any() matches sim flags
        for k in flag_keys:
            assert bool(np.asarray(got[3][k]).any()) == bool(ref[3][k])
            assert not np.asarray(got[3][k]).any()

        # a device-sampled plan trains end-to-end under shard_map for all
        # three models (spmd forward == sim forward on the same plan)
        mb = eng.sample_batch(targets, 0, 0)
        plan = build_split_plan(mb, part.assignment, NDEV)
        pa = plan_to_device(plan)
        feats = jnp.asarray(load_features(plan, ds.features))
        for model in ("sage", "gcn", "gat"):
            spec = GNNSpec(model=model, in_dim=ds.spec.feat_dim, hidden_dim=16,
                           out_dim=4, num_layers=2, num_heads=2)
            params = init_gnn_params(jax.random.PRNGKey(0), spec)
            ref_out = gnn_forward(spec, params, feats, pa, sim_shuffle)
            fwd = jax.jit(lambda prms, feats_in: gnn_forward_split_mesh(
                spec, mesh, prms, feats_in, pa))
            out = fwd(params, feats)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                       rtol=2e-5, atol=2e-5)
            # parameter gradients flow under shard_map (spmd "trains"):
            # matches the sim-mode parameter gradient on the same plan
            loss_spmd = lambda prms: (fwd(prms, feats) ** 2).sum()
            loss_sim = lambda prms: (
                gnn_forward(spec, prms, feats, pa, sim_shuffle) ** 2
            ).sum()
            g_spmd = jax.grad(loss_spmd)(params)
            g_sim = jax.grad(loss_sim)(params)
            for leaf, ref_leaf in zip(jax.tree_util.tree_leaves(g_spmd),
                                      jax.tree_util.tree_leaves(g_sim)):
                assert np.isfinite(np.asarray(leaf)).all()
                np.testing.assert_allclose(np.asarray(leaf),
                                           np.asarray(ref_leaf),
                                           rtol=5e-4, atol=5e-5)
            print(model, "OK")
        print("OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "OK" in out.stdout
