"""Fused gather->segment-aggregate kernels vs the jnp oracle (interpret mode).

Covers the whole vertical slice of the dst-sorted layout contract
(docs/KERNELS.md): op-level equivalence (fwd + grads), the layout invariants
a plan must satisfy, repad stability (HWM growth must not change numerics),
and model-level `agg_backend="pallas"` == `"jnp"` for all three GNNs.
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gather_segsum import layout, ops
from repro.kernels.gather_segsum.ref import (
    gather_segment_mean_ref,
    gather_segment_sum_ref,
    gather_weighted_segsum_ref,
)

TOL = dict(rtol=3e-5, atol=3e-5)
GRAD_TOL = dict(rtol=3e-4, atol=3e-4)


def _random_case(rng, E, M, F, N):
    dst = rng.integers(0, N, size=E).astype(np.int32)
    mask = rng.random(E) > 0.2
    src = rng.integers(0, M, size=E).astype(np.int32)
    mixed = jnp.asarray(rng.normal(size=(M, F)), jnp.float32)
    lay = layout.layer_layout(dst[None], mask[None], N)
    return (
        mixed,
        jnp.asarray(src),
        jnp.asarray(dst),
        jnp.asarray(mask),
        jnp.asarray(lay["pack_perm"][0]),
        jnp.asarray(lay["pack_dst"][0]),
        jnp.asarray(lay["seg_offsets"][0]),
    )


SHAPES = [
    (200, 60, 48, 90),
    (37, 10, 130, 10),  # non-aligned feature dim
    (513, 200, 1, 300),  # single feature column
    (5, 8, 8, 513),  # tiny edges, many destination blocks
    (1000, 300, 64, 257),
]


@pytest.mark.parametrize("E,M,F,N", SHAPES)
def test_fused_sum_and_mean_match_ref(E, M, F, N):
    rng = np.random.default_rng(E + M)
    mixed, src, dst, mask, pp, pd, so = _random_case(rng, E, M, F, N)
    out = ops.gather_segment_sum(mixed, src, pp, pd, N, interpret=True)
    ref = gather_segment_sum_ref(mixed, src, dst, mask, N)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
    outm = ops.gather_segment_mean(mixed, src, pp, pd, so, N, interpret=True)
    refm = gather_segment_mean_ref(mixed, src, dst, mask, N)
    np.testing.assert_allclose(np.asarray(outm), np.asarray(refm), **TOL)


@pytest.mark.parametrize("E,M,F,N", SHAPES[:3])
def test_fused_sum_grad_matches_ref(E, M, F, N):
    rng = np.random.default_rng(E)
    mixed, src, dst, mask, pp, pd, _ = _random_case(rng, E, M, F, N)
    g1 = jax.grad(
        lambda m: (ops.gather_segment_sum(m, src, pp, pd, N, interpret=True) ** 2).sum()
    )(mixed)
    g2 = jax.grad(
        lambda m: (gather_segment_sum_ref(m, src, dst, mask, N) ** 2).sum()
    )(mixed)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), **GRAD_TOL)


def test_fused_weighted_matches_ref_with_grads():
    rng = np.random.default_rng(3)
    E, M, H, dh, N = 300, 80, 4, 16, 120
    mixed, src, dst, mask, pp, pd, _ = _random_case(rng, E, M, H * dh, N)
    w = jnp.asarray(rng.normal(size=(E, H)), jnp.float32)
    out = ops.gather_weighted_segsum(mixed, w, src, pp, pd, N, interpret=True)
    ref = gather_weighted_segsum_ref(mixed, w, src, dst, mask, N)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
    gm1, gw1 = jax.grad(
        lambda m, w: (ops.gather_weighted_segsum(m, w, src, pp, pd, N, interpret=True) ** 2).sum(),
        argnums=(0, 1),
    )(mixed, w)
    gm2, gw2 = jax.grad(
        lambda m, w: (gather_weighted_segsum_ref(m, w, src, dst, mask, N) ** 2).sum(),
        argnums=(0, 1),
    )(mixed, w)
    np.testing.assert_allclose(np.asarray(gm1), np.asarray(gm2), **GRAD_TOL)
    np.testing.assert_allclose(np.asarray(gw1), np.asarray(gw2), **GRAD_TOL)


def test_bf16_storage_f32_accumulation():
    rng = np.random.default_rng(4)
    mixed, src, dst, mask, pp, pd, _ = _random_case(rng, 400, 100, 32, 150)
    m16 = mixed.astype(jnp.bfloat16)
    out = ops.gather_segment_sum(m16, src, pp, pd, 150, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = gather_segment_sum_ref(m16.astype(jnp.float32), src, dst, mask, 150)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=1e-2, atol=0.3
    )


# --------------------------------------------------------------------------- #
# layout contract invariants (docs/KERNELS.md)
# --------------------------------------------------------------------------- #
def test_layout_contract_invariants():
    rng = np.random.default_rng(5)
    P, E, N = 3, 777, 260
    dst = rng.integers(0, N, size=(P, E)).astype(np.int32)
    mask = rng.random((P, E)) > 0.3
    lay = layout.layer_layout(dst, mask, N)
    R = layout.AGG_ROWS
    for p in range(P):
        perm = lay["edge_perm"][p]
        # a true permutation of [0, E)
        assert sorted(perm.tolist()) == list(range(E))
        nv = int(mask[p].sum())
        # valid edges first, dst-nondecreasing over the valid prefix
        assert mask[p][perm[:nv]].all() and not mask[p][perm[nv:]].any()
        sorted_dst = dst[p][perm[:nv]]
        assert (np.diff(sorted_dst) >= 0).all()
        # CSR offsets index the dst-sorted order exactly
        off = lay["seg_offsets"][p]
        assert off[0] == 0 and off[-1] == nv
        for n in (0, N // 2, N - 1):
            seg = sorted_dst[off[n]:off[n + 1]]
            assert (seg == n).all()
        # pack: every valid edge in its dst row-block, sentinels elsewhere
        pp, pd = lay["pack_perm"][p], lay["pack_dst"][p]
        filled = pd < R
        assert filled.sum() == nv
        db_idx = np.nonzero(filled)[0]
        e_idx = pp[filled]
        np.testing.assert_array_equal(dst[p][e_idx] // R, db_idx)
        np.testing.assert_array_equal(dst[p][e_idx] % R, pd[filled])


def test_repad_preserves_fused_results():
    """HWM growth (E, N, EB, DB axes) must leave fused numerics correct.

    ``edge_src`` is rebased onto the grown mixed-buffer layout by
    ``repad_plan``, so the check is fused == jnp-ref *on the repadded plan
    itself* (per layer, per device), plus exact zeros beyond the original
    destination rows. A stale dst-sorted layout (e.g. zero-filled instead of
    sentinel-filled pack blocks) fails this immediately.
    """
    from repro.core import build_split_plan, partition_graph, presample
    from repro.graph.datasets import make_dataset
    from repro.graph.sampling import sample_minibatch
    from repro.core.splitting import repad_plan

    ds = make_dataset("tiny")
    rng = np.random.default_rng(0)
    mb = sample_minibatch(ds.graph, ds.train_ids[:24], [4, 4], rng)
    w = presample(ds.graph, ds.train_ids, [4, 4], 24, num_epochs=1)
    part = partition_graph(ds.graph, 4, method="gsplit", weights=w)
    plan = build_split_plan(mb, part.assignment, 4)
    orig_out = [lp.self_pos.shape[1] for lp in plan.layers]
    plan = copy.deepcopy(plan)
    hwm = {
        "N0": 64, "N1": 192, "N2": 512, "E0": 1024, "E1": 1024,
        "S0": 48, "S1": 48, "EB0": 128, "EB1": 128,
    }
    repad_plan(plan, hwm)

    for li, lp in enumerate(plan.layers):
        # EB axis growth is a pure append inside each block
        assert lp.pack_perm.shape[2] == hwm[f"EB{li}"]
        num_out = lp.self_pos.shape[1]
        mwidth = lp.n_local + plan.num_devices * lp.send_idx.shape[2]
        for dev in range(plan.num_devices):
            mixed = jnp.asarray(
                np.random.default_rng(dev).normal(size=(mwidth, 12)),
                jnp.float32,
            )
            fused = ops.gather_segment_sum(
                mixed, jnp.asarray(lp.edge_src[dev]),
                jnp.asarray(lp.pack_perm[dev]),
                jnp.asarray(lp.pack_dst[dev]), num_out, interpret=True,
            )
            ref = gather_segment_sum_ref(
                mixed, jnp.asarray(lp.edge_src[dev]),
                jnp.asarray(lp.edge_dst[dev]),
                jnp.asarray(lp.edge_mask[dev]), num_out,
            )
            np.testing.assert_allclose(np.asarray(fused), np.asarray(ref), **TOL)
            assert not np.asarray(fused[orig_out[li]:]).any()


# --------------------------------------------------------------------------- #
# model-level equivalence: agg_backend="pallas" == "jnp", sim path
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("model", ["sage", "gcn", "gat"])
def test_gnn_forward_backend_equivalence(model):
    from dataclasses import replace

    from repro.core import build_split_plan, partition_graph, presample, sim_shuffle
    from repro.graph.datasets import make_dataset
    from repro.graph.sampling import sample_minibatch
    from repro.models.gnn import GNNSpec, init_gnn_params
    from repro.models.gnn.layers import gnn_forward
    from repro.train.loss import masked_softmax_xent
    from repro.train.plan_io import load_features, load_labels, plan_to_device

    ds = make_dataset("tiny")
    rng = np.random.default_rng(7)
    mb = sample_minibatch(ds.graph, ds.train_ids[:32], [4, 4], rng)
    w = presample(ds.graph, ds.train_ids, [4, 4], 32, num_epochs=2)
    part = partition_graph(ds.graph, 4, method="gsplit", weights=w)
    plan = build_split_plan(mb, part.assignment, 4)

    spec_j = GNNSpec(
        model=model, in_dim=ds.spec.feat_dim, hidden_dim=16, out_dim=8,
        num_layers=2, num_heads=2,
    )
    spec_p = replace(spec_j, agg_backend="pallas")
    params = init_gnn_params(jax.random.PRNGKey(0), spec_j)
    pa = plan_to_device(plan)
    feats = jnp.asarray(load_features(plan, ds.features))
    labels = jnp.asarray(load_labels(plan, ds.labels))

    def loss(p, spec):
        logits = gnn_forward(spec, p, feats, pa, sim_shuffle)
        return masked_softmax_xent(logits, labels, pa["target_mask"])

    lj, gj = jax.value_and_grad(lambda p: loss(p, spec_j))(params)
    lp_, gp = jax.value_and_grad(lambda p: loss(p, spec_p))(params)
    np.testing.assert_allclose(float(lj), float(lp_), rtol=2e-5, atol=1e-6)
    for a, b in zip(
        jax.tree_util.tree_leaves(gj), jax.tree_util.tree_leaves(gp)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5
        )

    # the same batch repadded to larger HWMs: padding must be inert for the
    # jnp backend (existing invariant) AND for the fused layout
    from repro.core.splitting import repad_plan

    plan2 = copy.deepcopy(plan)
    hwm = {
        "N0": 48, "N1": 160, "N2": 300, "E0": 640, "E1": 640,
        "S0": 32, "S1": 32, "EB0": 64, "EB1": 64,
    }
    repad_plan(plan2, hwm)
    pa2 = plan_to_device(plan2)
    feats2 = jnp.asarray(load_features(plan2, ds.features))
    labels2 = jnp.asarray(load_labels(plan2, ds.labels))

    def loss2(p, spec):
        logits = gnn_forward(spec, p, feats2, pa2, sim_shuffle)
        return masked_softmax_xent(logits, labels2, pa2["target_mask"])

    lj2 = float(loss2(params, spec_j))
    lp2 = float(loss2(params, spec_p))
    np.testing.assert_allclose(float(lj), lj2, rtol=1e-6)
    np.testing.assert_allclose(lj2, lp2, rtol=2e-5, atol=1e-6)


# --------------------------------------------------------------------------- #
# property-based sweep (skips cleanly without hypothesis)
# --------------------------------------------------------------------------- #
from repro.testing import given, settings, st  # hypothesis or fallback

HAVE_HYPOTHESIS = True  # repro.testing provides a deterministic fallback

if HAVE_HYPOTHESIS:

    @settings(deadline=None, max_examples=12)
    @given(
        E=st.integers(min_value=1, max_value=400),
        M=st.integers(min_value=1, max_value=150),
        F=st.integers(min_value=1, max_value=80),
        N=st.integers(min_value=1, max_value=280),
        grow=st.booleans(),
        seed=st.integers(min_value=0, max_value=50),
    )
    def test_fused_property(E, M, F, N, grow, seed):
        """fused == ref for random graphs/masks/paddings, with and without
        repadding the layout to a larger high-water mark."""
        rng = np.random.default_rng(seed)
        dst = rng.integers(0, N, size=E).astype(np.int32)
        mask = rng.random(E) > rng.random() * 0.8
        src = rng.integers(0, M, size=E).astype(np.int32)
        mixed = jnp.asarray(rng.normal(size=(M, F)), jnp.float32)
        lay = layout.layer_layout(dst[None], mask[None], N)
        pp, pd = lay["pack_perm"][0], lay["pack_dst"][0]
        num_out = N
        if grow:
            # simulate HWM repad: grow EB and DB with sentinel appends
            from repro.core.splitting import pad_axis_fill

            R = layout.AGG_ROWS
            eb2 = pp.shape[1] * 2
            db2 = pp.shape[0] + 2
            num_out = db2 * R  # any num_out the grown DB covers
            pp = pad_axis_fill(pad_axis_fill(pp, 1, eb2, E), 0, db2, E)
            pd = pad_axis_fill(pad_axis_fill(pd, 1, eb2, R), 0, db2, R)
        out = ops.gather_segment_sum(
            mixed, jnp.asarray(src), jnp.asarray(pp), jnp.asarray(pd), num_out,
            interpret=True,
        )
        ref = gather_segment_sum_ref(
            mixed, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask), N
        )
        np.testing.assert_allclose(
            np.asarray(out[:N]), np.asarray(ref), **TOL
        )
        assert not np.asarray(out[N:]).any()
