"""Device-resident cache serving: the served input-feature block must be
bit-identical to a full host gather for every placement, including after
high-water-mark repadding, and ``partitioned`` placement must never produce
a remote hit on plans split by the same assignment."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.partition import partition_graph
from repro.core.presample import presample
from repro.core.shuffle import sim_serve_features
from repro.core.splitting import build_split_plan, repad_plan
from repro.graph.cache import FeatureCache
from repro.graph.datasets import make_dataset
from repro.graph.sampling import sample_minibatch
from repro.models.gnn import GNNSpec
from repro.testing import PoisonedBlockPool
from repro.train.plan_io import (
    cache_plan_to_device,
    load_features,
    load_miss_features,
)
from repro.train.trainer import TrainConfig, Trainer

NDEV = 4


@pytest.fixture(scope="module")
def setup():
    ds = make_dataset("tiny")
    w = presample(ds.graph, ds.train_ids, [4, 4], 32, num_epochs=2)
    part = partition_graph(ds.graph, NDEV, method="gsplit", weights=w, seed=0)
    return ds, w, part


def _cache(ds, w, part, mode, capacity):
    return FeatureCache(
        ds.graph.num_nodes, NDEV, capacity, ranking=w.vertex_weight,
        mode=mode, partition_assignment=part.assignment,
    )


def _serve(cache, plan, features):
    cp = cache.build_plan(plan)
    block = jnp.asarray(cache.build_resident(features))
    miss = load_miss_features(cp, features)
    got = sim_serve_features(block, cache_plan_to_device(cp), jnp.asarray(miss))
    return np.asarray(got), cp


@pytest.mark.parametrize(
    "mode,capacity",
    [
        ("partitioned", 1_000_000),  # everything cached
        ("partitioned", 16),  # partial: misses present
        ("distributed", 16),  # partial: local + remote + miss
        ("distributed", 1_000_000),
    ],
)
def test_served_block_equals_host_gather(setup, mode, capacity):
    ds, w, part = setup
    cache = _cache(ds, w, part, mode, capacity)
    rng = np.random.default_rng(1)
    mb = sample_minibatch(ds.graph, ds.train_ids[:32], [4, 4], rng)
    plan = build_split_plan(mb, part.assignment, NDEV)
    got, cp = _serve(cache, plan, ds.features)
    want = load_features(plan, ds.features)
    np.testing.assert_array_equal(got, want)
    # every required row is classified exactly once
    bd = cp.breakdown()
    assert bd.total == plan.loaded_feature_rows()
    assert bd == cache.classify_plan(plan)


def test_partitioned_cache_zero_remote_hits(setup):
    """Partition-consistent placement: a split plan built from the same
    assignment can only hit its own device's block."""
    ds, w, part = setup
    cache = _cache(ds, w, part, "partitioned", 1_000_000)
    rng = np.random.default_rng(2)
    for _ in range(3):
        targets = rng.choice(ds.train_ids, size=24, replace=False)
        mb = sample_minibatch(ds.graph, targets, [4, 4], rng)
        plan = build_split_plan(mb, part.assignment, NDEV)
        cp = cache.build_plan(plan)
        bd = cp.breakdown()
        assert bd.remote_hit == 0
        assert not cp.recv_mask.any()
        assert bd.local_hit == plan.loaded_feature_rows()


def test_distributed_cache_has_remote_hits(setup):
    ds, w, part = setup
    cache = _cache(ds, w, part, "distributed", 32)
    rng = np.random.default_rng(3)
    mb = sample_minibatch(ds.graph, ds.train_ids[:32], [4, 4], rng)
    plan = build_split_plan(mb, part.assignment, NDEV)
    cp = cache.build_plan(plan)
    assert cp.breakdown().remote_hit > 0  # hot rows live on peer devices


@pytest.mark.parametrize("mode", ["partitioned", "distributed"])
def test_served_block_exact_after_repad(setup, mode):
    """The delivery-side repad (plan + cache plan) must not perturb serving
    — the same invariant the runtime's ``_finalize`` relies on."""
    ds, w, part = setup
    cache = _cache(ds, w, part, mode, 24)
    rng = np.random.default_rng(4)
    big = sample_minibatch(ds.graph, ds.train_ids[:48], [4, 4], rng)
    small = sample_minibatch(ds.graph, ds.train_ids[48:60], [4, 4], rng)

    hwm = {}
    big_plan = build_split_plan(big, part.assignment, NDEV)
    repad_plan(big_plan, hwm)
    big_cp = cache.build_plan(big_plan)
    hwm["CM"], hwm["CS"] = big_cp.max_miss, big_cp.max_send

    plan = build_split_plan(small, part.assignment, NDEV)
    repad_plan(plan, hwm)
    cp = cache.build_plan(plan)
    hwm["CM"] = max(hwm["CM"], cp.max_miss)
    hwm["CS"] = max(hwm["CS"], cp.max_send)
    cp.pad_to(plan.front_ids[-1].shape[1], hwm["CM"], hwm["CS"])

    block = jnp.asarray(cache.build_resident(ds.features))
    miss = load_miss_features(cp, ds.features)
    got = np.asarray(
        sim_serve_features(block, cache_plan_to_device(cp), jnp.asarray(miss))
    )
    np.testing.assert_array_equal(got, load_features(plan, ds.features))


def test_trainer_serving_matches_accounting_only(setup):
    """End-to-end: the served trainer walks the exact float trajectory of
    the accounting-only (full host gather) trainer, while loading far fewer
    host rows."""
    ds, _, _ = setup
    spec = GNNSpec(
        model="sage", in_dim=ds.spec.feat_dim, hidden_dim=16,
        out_dim=ds.spec.num_classes, num_layers=2,
    )

    def run(serve: bool, pool=None):
        cfg = TrainConfig(
            mode="split", num_devices=NDEV, fanouts=(4, 4), batch_size=32,
            presample_epochs=2, seed=7, cache_mode="partitioned",
            cache_capacity_per_device=ds.graph.num_nodes,
            cache_serve=serve, plan_source="pipelined",
        )
        tr = Trainer(ds, spec, cfg)
        # the served side's miss blocks come from a pool that poisons each
        # block it takes back (repro.testing); the plain side gathers fresh
        tr.producer.pool = pool
        if pool is not None:
            tr.feature_pool = pool
        traj, totals = [], None
        for _ in range(2):
            st = tr.train_epoch(max_iters=3)
            traj += [(i.loss, i.accuracy) for i in st.iters]
            totals = st.totals()
        return traj, totals

    pool = PoisonedBlockPool()
    served_traj, served_tot = run(True, pool)
    plain_traj, plain_tot = run(False)
    assert pool.reused > 0
    assert served_traj == plain_traj
    # fully-cached partitioned placement: zero host rows on the serving path
    assert served_tot["load_host_miss"] == 0
    assert served_tot["load_local_hit"] == served_tot["loaded_rows"]
    assert plain_tot["loaded_rows"] == served_tot["loaded_rows"]
