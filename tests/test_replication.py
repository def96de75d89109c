"""Hot-vertex replication correctness (DESIGN.md "Partitioning & replication").

The locality invariant under test: an edge whose source is replicated is
served from the static resident block on every split — rerouted into the
``[local | recv | replicated]`` mixed-buffer layout — and must produce
exactly the math of the non-replicated plan. Coverage:

  * replicated == non-replicated forward on all 3 models x jnp/pallas x
    blocking/overlap (bitwise for the blocking jnp path: same edge order,
    same gathered bits),
  * dp training trajectories bitwise unchanged by the knob,
  * sim == spmd with replication on (subprocess, forced host devices),
  * repad/HWM growth preserves replicated-plan semantics (property test).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax

from repro.core import build_split_plan, partition_graph, presample, sim_shuffle
from repro.core.splitting import repad_plan
from repro.graph.datasets import make_dataset
from repro.graph.sampling import sample_minibatch
from repro.models.gnn import GNNSpec, init_gnn_params
from repro.models.gnn.layers import gnn_forward
from repro.train.plan_io import load_features, plan_to_device
from repro.train.trainer import TrainConfig, Trainer

NDEV = 4
BUDGET = 0.10  # tiny graph: a 5% budget replicates too few rows to exercise
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(scope="module")
def setup():
    ds = make_dataset("tiny")
    w = presample(ds.graph, ds.train_ids, [4, 4], 32, num_epochs=2)
    part = partition_graph(
        ds.graph, NDEV, method="gsplit", weights=w, seed=0,
        replication_budget=BUDGET,
    )
    assert part.replication is not None
    rng = np.random.default_rng(3)
    mb = sample_minibatch(ds.graph, ds.train_ids[:32], [4, 4], rng)
    return ds, part, mb


def _forwards(ds, part, mb, spec):
    """(non-replicated out, replicated out) for one spec on one minibatch."""
    halves = spec.overlap
    plan0 = build_split_plan(mb, part.assignment, NDEV, with_halves=halves)
    plan1 = build_split_plan(
        mb, part.assignment, NDEV, with_halves=halves,
        replication=part.replication,
    )
    # replication changes edge addressing, never the frontiers or loads
    for f0, f1 in zip(plan0.front_ids, plan1.front_ids):
        np.testing.assert_array_equal(f0, f1)
    assert plan1.shuffle_rows() < plan0.shuffle_rows()

    feats = jnp.asarray(load_features(plan0, ds.features))
    rep_block = jnp.asarray(
        ds.features[part.replication.vertices].astype(np.float32)
    )
    params = init_gnn_params(jax.random.PRNGKey(0), spec)
    out0 = gnn_forward(
        spec, params, feats, plan_to_device(plan0, with_halves=halves),
        sim_shuffle,
    )
    out1 = gnn_forward(
        spec, params, feats,
        plan_to_device(
            plan1, with_halves=halves,
            num_replicated=part.replication.num_replicated,
        ),
        sim_shuffle, rep_block=rep_block,
    )
    return np.asarray(out0), np.asarray(out1)


@pytest.mark.parametrize("overlap", [False, True], ids=["blocking", "overlap"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("model", ["sage", "gcn", "gat"])
def test_replicated_matches_nonreplicated_forward(
    setup, model, backend, overlap
):
    ds, part, mb = setup
    spec = GNNSpec(
        model=model, in_dim=ds.spec.feat_dim, hidden_dim=16, out_dim=4,
        num_layers=2, num_heads=2, agg_backend=backend,
        overlap=overlap, shuffle_chunks=2 if overlap else 1,
    )
    out0, out1 = _forwards(ds, part, mb, spec)
    if backend == "jnp" and not overlap:
        # same edge order, same gathered bits: bit-identical
        np.testing.assert_array_equal(out1, out0)
    else:
        # half membership / pack layout reassociate the edge reduction
        np.testing.assert_allclose(out1, out0, rtol=2e-5, atol=2e-5)


def test_replication_consistency_guard(setup):
    """A replicated plan staged without the matching block height is a
    silent wrong-gather — plan_to_device must reject the mismatch."""
    ds, part, mb = setup
    plan = build_split_plan(
        mb, part.assignment, NDEV, replication=part.replication
    )
    with pytest.raises(ValueError, match="replicated"):
        plan_to_device(plan)  # num_replicated defaults to 0
    plan0 = build_split_plan(mb, part.assignment, NDEV)
    with pytest.raises(ValueError, match="replicated"):
        plan_to_device(plan0, num_replicated=part.replication.num_replicated)


def test_dp_trajectory_bitwise_unchanged_by_replication_knob():
    """dp (and pushpull) plans never consult the replication set; the config
    knob must not perturb their training trajectories in any bit."""
    ds = make_dataset("tiny")
    spec = GNNSpec(
        model="sage", in_dim=ds.spec.feat_dim, hidden_dim=16,
        out_dim=ds.spec.num_classes, num_layers=2,
    )

    def losses(budget):
        cfg = TrainConfig(
            mode="dp", num_devices=4, fanouts=(4, 4), batch_size=32,
            presample_epochs=2, replication_budget=budget, seed=5,
        )
        tr = Trainer(ds, spec, cfg)
        return [tr.train_iter(ds.train_ids[i * 32:(i + 1) * 32]).loss
                for i in range(3)]

    assert losses(0.0) == losses(0.25)


def test_split_trainer_loss_matches_without_replication():
    """End-to-end split-mode trainer: identical losses with and without
    replication (blocking jnp path: bit-identical), smaller wire bytes."""
    ds = make_dataset("tiny")
    spec = GNNSpec(
        model="sage", in_dim=ds.spec.feat_dim, hidden_dim=16,
        out_dim=ds.spec.num_classes, num_layers=2,
    )

    def run(budget):
        cfg = TrainConfig(
            mode="split", num_devices=4, fanouts=(4, 4), batch_size=64,
            presample_epochs=2, replication_budget=budget, seed=0,
        )
        tr = Trainer(ds, spec, cfg)
        return tr.train_epoch(max_iters=2)

    s0, s1 = run(0.0), run(BUDGET)
    assert [i.loss for i in s0.iters] == [i.loss for i in s1.iters]
    assert sum(i.wire_bytes for i in s1.iters) < sum(
        i.wire_bytes for i in s0.iters
    )
    assert all(
        a.cross_edge_fraction <= b.cross_edge_fraction
        for a, b in zip(s1.iters, s0.iters)
    )


def test_sim_matches_spmd_with_replication():
    """shard_map execution with the replicated block (all-None specs —
    identical on every device) == sim mode, blocking and overlap."""
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.graph.datasets import make_dataset
        from repro.graph.sampling import sample_minibatch
        from repro.core import (
            presample, partition_graph, build_split_plan, sim_shuffle,
        )
        from repro.launch.sharding import make_split_mesh, replicated_block_specs
        from repro.models.gnn import GNNSpec, init_gnn_params
        from repro.models.gnn.layers import gnn_forward, gnn_forward_split_mesh
        from repro.train.plan_io import plan_to_device, load_features

        NDEV = 4
        ds = make_dataset("tiny")
        rng = np.random.default_rng(0)
        mb = sample_minibatch(ds.graph, ds.train_ids[:16], [3, 3], rng)
        w = presample(ds.graph, ds.train_ids, [3, 3], 16, num_epochs=1)
        part = partition_graph(ds.graph, NDEV, method="gsplit", weights=w,
                               replication_budget=0.10)
        rep = part.replication
        assert rep is not None
        rep_block = jnp.asarray(
            ds.features[rep.vertices].astype(np.float32))
        (rep_spec,) = replicated_block_specs((rep_block,))
        assert rep_spec == P(None, None)
        mesh = make_split_mesh(1, NDEV)

        for overlap in (False, True):
            plan = build_split_plan(mb, part.assignment, NDEV,
                                    with_halves=overlap, replication=rep)
            pa = plan_to_device(plan, with_halves=overlap,
                                num_replicated=rep.num_replicated)
            feats = jnp.asarray(load_features(plan, ds.features))
            spec = GNNSpec(model="sage", in_dim=ds.spec.feat_dim,
                           hidden_dim=16, out_dim=4, num_layers=2,
                           overlap=overlap)
            params = init_gnn_params(jax.random.PRNGKey(0), spec)
            ref = gnn_forward(spec, params, feats, pa, sim_shuffle,
                              rep_block=rep_block)
            got = jax.jit(lambda f, rb: gnn_forward_split_mesh(
                spec, mesh, params, f, pa, rep_block=rb))(feats, rep_block)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)
            print("overlap", overlap, "OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"


# --------------------------------------------------------------------------- #
# repad/HWM growth with shrunken remote halves
# --------------------------------------------------------------------------- #
def _masked_out(plan, out):
    """Forward output at valid target slots only (padding rows excluded)."""
    mask = plan.node_mask[0]
    return np.asarray(out)[: mask.shape[0], : mask.shape[1]][mask]


from repro.testing import given, settings, st  # noqa: E402


@settings(max_examples=8, deadline=None)
@given(
    lo=st.integers(0, 40),
    width=st.integers(4, 24),
    seed=st.integers(0, 1000),
)
def test_repadded_replicated_plans_preserve_forward(setup, lo, width, seed):
    """Property: any small batch repadded to a larger batch's high-water
    marks computes the same forward as its freshly-built plan — with
    replication on and the overlap halves shipped. Exercises the three-way
    edge_src rebase (local / recv divmod / replicated shift) and the
    ledge_src rebase for the local half that now contains replicated rows."""
    ds, part, _ = setup
    rng = np.random.default_rng(seed)
    big = sample_minibatch(ds.graph, ds.train_ids[:48], [4, 4], rng)
    small_ids = ds.train_ids[lo : lo + width]
    small = sample_minibatch(ds.graph, small_ids, [4, 4], rng)
    rep = part.replication

    spec = GNNSpec(
        model="sage", in_dim=ds.spec.feat_dim, hidden_dim=16, out_dim=4,
        num_layers=2, overlap=True, shuffle_chunks=2,
    )
    params = init_gnn_params(jax.random.PRNGKey(1), spec)

    hwm: dict = {}
    big_plan = build_split_plan(
        big, part.assignment, NDEV, with_halves=True, replication=rep
    )
    repad_plan(big_plan, hwm)

    fresh = build_split_plan(
        small, part.assignment, NDEV, with_halves=True, replication=rep
    )
    repadded = build_split_plan(
        small, part.assignment, NDEV, with_halves=True, replication=rep
    )
    repad_plan(repadded, hwm)

    # plan statistics are invariant under repadding
    assert repadded.cross_edge_fraction() == fresh.cross_edge_fraction()
    assert repadded.shuffle_rows() == fresh.shuffle_rows()
    assert repadded.computed_edges() == fresh.computed_edges()
    # only the bottom (input) layer serves rows from the resident block
    assert repadded.layers[-1].num_replicated == rep.num_replicated
    assert all(lp.num_replicated == 0 for lp in repadded.layers[:-1])

    rep_block = jnp.asarray(ds.features[rep.vertices].astype(np.float32))
    outs = []
    for plan in (fresh, repadded):
        feats = jnp.asarray(load_features(plan, ds.features))
        out = gnn_forward(
            spec, params, feats,
            plan_to_device(
                plan, with_halves=True, num_replicated=rep.num_replicated
            ),
            sim_shuffle, rep_block=rep_block,
        )
        outs.append(_masked_out(fresh, out))
    np.testing.assert_allclose(outs[1], outs[0], rtol=2e-5, atol=2e-5)
