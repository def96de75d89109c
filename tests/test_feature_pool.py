"""The host feature-block pool (``train.plan_io.FeatureBlockPool``) and the
gathers that write into its blocks: reuse, thread-safety, collection of
blocks never released, bit-identity with a fresh gather for every plan kind,
and the trainer's release discipline under pipelining and fault injection."""
import gc
import threading
import weakref
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_dp_plan, build_split_plan, partition_graph, presample
from repro.faults import FaultAction, FaultInjector
from repro.graph.cache import FeatureCache
from repro.graph.datasets import make_dataset
from repro.graph.sampling import NeighborSampler
from repro.models.gnn import GNNSpec
from repro.testing import PoisonedBlockPool
from repro.train.plan_io import (
    FeatureBlockPool,
    load_features,
    load_miss_features,
    stage_host_features,
)
from repro.train.trainer import TrainConfig, Trainer

F32 = np.float32


@pytest.fixture(scope="module")
def ds():
    return make_dataset("tiny")


# --------------------------------------------------------------------- #
# the pool
# --------------------------------------------------------------------- #
def test_released_block_is_reused_for_its_key():
    pool = FeatureBlockPool()
    a, reused = pool.acquire((2, 8, 4), F32)
    assert not reused and a.shape == (2, 8, 4) and a.dtype == F32
    assert pool.release(a)
    b, reused = pool.acquire((2, 8, 4), np.dtype("float32"))
    assert reused and b is a


@pytest.mark.parametrize(
    "shape,dtype", [((2, 16, 4), F32), ((2, 8, 4), np.float64)],
    ids=["new shape", "new dtype"],
)
def test_new_key_gets_a_new_block(shape, dtype):
    pool = FeatureBlockPool()
    a, _ = pool.acquire((2, 8, 4), F32)
    pool.release(a)
    b, reused = pool.acquire(shape, dtype)
    assert not reused and b is not a
    assert b.shape == shape and b.dtype == dtype


def test_a_held_block_is_never_handed_out_twice():
    pool = FeatureBlockPool()
    a, _ = pool.acquire((4, 4), F32)
    b, reused = pool.acquire((4, 4), F32)
    assert not reused and b is not a
    assert pool.release(a)
    assert not pool.release(a)  # a second release keeps nothing
    c, reused_c = pool.acquire((4, 4), F32)
    d, reused_d = pool.acquire((4, 4), F32)
    assert (reused_c, reused_d) == (True, False)
    assert c is a and d is not a and d is not b


def test_release_ignores_arrays_it_did_not_hand_out():
    pool = FeatureBlockPool()
    a, _ = pool.acquire((4, 4), F32)
    assert not pool.release(np.zeros((4, 4), F32))  # e.g. a padded copy
    assert not pool.release(a[:2])  # a view of a held block
    _, reused = pool.acquire((4, 4), F32)
    assert not reused


def test_unreleased_block_is_collected():
    """A block dropped without a release (a killed producer, a batch that
    ``close`` dropped) is garbage like any array: the pool keeps no strong
    reference to a block it handed out."""
    pool = FeatureBlockPool()
    a, _ = pool.acquire((64, 8), F32)
    ref = weakref.ref(a)
    del a
    gc.collect()
    assert ref() is None
    _, reused = pool.acquire((64, 8), F32)
    assert not reused


def test_two_producer_threads_never_share_a_block():
    pool = FeatureBlockPool()
    errors, reuses = [], []
    start = threading.Barrier(2)

    def producer(tag: float):
        start.wait()
        n = 0
        for _ in range(400):
            block, reused = pool.acquire((8, 16), F32)
            n += reused
            block.fill(tag)
            # hold the block across a switch: the other thread must not
            # get it until it is released
            threading.Event().wait(0)
            if not (block == tag).all():
                errors.append(tag)
            pool.release(block)
        reuses.append(n)

    threads = [threading.Thread(target=producer, args=(t,)) for t in (1.0, 2.0)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    assert sum(reuses) >= 800 - 2  # at most one allocation per thread


def test_poisoned_pool_blocks_are_visible_through_a_live_device_array():
    """The premise of the trainer tests below: on the CPU backend a staged
    array may alias its host block, so a block reused while its device
    array lives shows through it. The test pool's aligned blocks alias."""
    pool = PoisonedBlockPool()
    block, _ = pool.acquire((1, 64, 16), F32)
    block[...] = 1.0
    staged = jnp.asarray(block)
    pool.release(block)  # fills the block with NaN
    assert np.isnan(np.asarray(staged)).all()


# --------------------------------------------------------------------- #
# the pooled gathers, bit for bit against a fresh gather
# --------------------------------------------------------------------- #
def _fresh(features, ids, mask):
    rows = features[ids].astype(F32)
    rows[~mask] = 0.0
    return rows


def _bits(a):
    return np.ascontiguousarray(a).view(np.uint32)


def _recycle(pool, shape):
    """Leave a NaN-filled block of ``shape`` free in a PoisonedBlockPool."""
    pool.release(pool.acquire(shape, F32)[0])


def _pooled(plan, features, **kw):
    """The load stage's block from a pool whose free block is all NaN."""
    pool = PoisonedBlockPool()
    ids = kw["cache"].build_plan(plan).miss_ids if kw else plan.front_ids[-1]
    _recycle(pool, (*ids.shape, features.shape[1]))
    cp, feats, _, reused = stage_host_features(plan, features, pool=pool, **kw)
    assert reused
    return cp, feats


def _plans(ds):
    sampler = NeighborSampler(ds.graph, ds.train_ids, [4, 4], 32, seed=3)
    targets = sampler.epoch_targets(0)[0]
    w = presample(ds.graph, ds.train_ids, [4, 4], 32, num_epochs=1)
    part = partition_graph(ds.graph, 4, method="gsplit", weights=w, seed=0)
    dp = build_dp_plan(sampler.sample_micro_batch(targets, 4, 0, 0))
    split = build_split_plan(sampler.sample_batch(targets, 0, 0),
                             part.assignment, 4)
    return {"dp": dp, "split": split}, w, part


@pytest.mark.parametrize("kind", ["dp", "split"])
def test_pooled_gather_equals_fresh_gather(ds, kind):
    plan = _plans(ds)[0][kind]
    ids, mask = plan.front_ids[-1], plan.node_mask[-1]
    assert (~mask).any()  # the plan has padding to zero
    want = _bits(_fresh(ds.features, ids, mask))
    np.testing.assert_array_equal(_bits(load_features(plan, ds.features)), want)
    _, fresh, _, reused = stage_host_features(plan, ds.features)
    assert not reused
    np.testing.assert_array_equal(_bits(fresh), want)
    np.testing.assert_array_equal(_bits(_pooled(plan, ds.features)[1]), want)


@pytest.mark.parametrize("mode", ["partitioned", "distributed"])
def test_pooled_miss_gather_equals_fresh_gather(ds, mode):
    plans, w, part = _plans(ds)
    plan = plans["split"]
    cache = FeatureCache(ds.graph.num_nodes, 4, 16, ranking=w.vertex_weight,
                         mode=mode, partition_assignment=part.assignment)
    cp = cache.build_plan(plan)
    assert cp.miss_mask.any() and (~cp.miss_mask).any()
    want = _bits(_fresh(ds.features, cp.miss_ids, cp.miss_mask))
    np.testing.assert_array_equal(_bits(load_miss_features(cp, ds.features)),
                                  want)
    got_cp, got = _pooled(plan, ds.features, cache=cache, serve_cache=True)
    assert got_cp is not None
    np.testing.assert_array_equal(_bits(got), want)


def _fake_plan(ids, mask):
    return SimpleNamespace(front_ids=[ids], node_mask=[mask])


def test_pooled_gather_with_padding_inside_the_rows():
    """Padding need not be each device's tail: the masked write zeroes it
    wherever it lies."""
    rng = np.random.default_rng(0)
    features = rng.standard_normal((50, 6)).astype(F32)
    ids = rng.integers(0, 50, size=(3, 20))
    mask = rng.random((3, 20)) < 0.7
    mask[0, -1] = True  # device 0's padding is not a tail
    got = _pooled(_fake_plan(ids, mask), features)[1]
    np.testing.assert_array_equal(_bits(got), _bits(_fresh(features, ids, mask)))


def test_pooled_gather_casts_like_a_fresh_gather():
    features = np.random.default_rng(1).standard_normal((30, 5))  # float64
    ids = np.array([[3, 29, 0, 0]])
    mask = np.array([[True, True, False, False]])
    got = _pooled(_fake_plan(ids, mask), features)[1]
    np.testing.assert_array_equal(_bits(got), _bits(_fresh(features, ids, mask)))


@pytest.mark.parametrize("bad", [30, -1])
@pytest.mark.parametrize("pool", [None, FeatureBlockPool()], ids=["fresh", "pooled"])
def test_gather_rejects_ids_outside_the_table(bad, pool):
    plan = _fake_plan(np.array([[1, bad]]), np.array([[True, True]]))
    with pytest.raises(IndexError):
        stage_host_features(plan, np.zeros((30, 5), F32), pool=pool)


# --------------------------------------------------------------------- #
# the trainer: released blocks are dead to the device, and fully rewritten
# --------------------------------------------------------------------- #
def _spec(ds):
    return GNNSpec(
        model="sage", in_dim=ds.spec.feat_dim, hidden_dim=16,
        out_dim=ds.spec.num_classes, num_layers=2,
    )


def _trainer(ds, pool, injector=None, **kw):
    cfg = dict(mode="split", num_devices=4, fanouts=(4, 4), batch_size=16,
               presample_epochs=1, pipeline_depth=3, plan_workers=2, seed=7)
    cfg.update(kw)
    tr = Trainer(ds, _spec(ds), TrainConfig(**cfg), injector=injector)
    # pool None: the producers gather into fresh arrays, which the
    # trainer's pool then ignores on release
    tr.producer.pool = pool
    if pool is not None:
        tr.feature_pool = pool
    return tr


def _losses(tr, epochs=2, iters=4):
    return [(i.loss, i.accuracy) for _ in range(epochs)
            for i in tr.train_epoch(max_iters=iters).iters]


def test_pipelined_pool_under_fault_injection_matches_serial(ds):
    """Crashed, retried and delayed builds (fault injection) with a pool
    that poisons each block it takes back: losses bit-identical to the
    serial source with fresh arrays over two epochs."""
    want = _losses(_trainer(ds, None, plan_source="serial"))
    inj = FaultInjector(schedule=[
        FaultAction("crash", epoch=0, batch=1),
        FaultAction("transient", epoch=0, batch=2, times=1),
        FaultAction("delay", epoch=1, batch=0, delay_s=0.05),
        FaultAction("crash", epoch=1, batch=3),
    ])
    pool = PoisonedBlockPool()
    tr = _trainer(ds, pool, inj, plan_source="pipelined", plan_retries=2,
                  plan_retry_backoff_s=0.001)
    got = _losses(tr)
    assert len(inj.fired) == 4
    assert pool.reused > 0 and np.isfinite([l for l, _ in got]).all()
    assert got == want


def test_killed_epoch_leaves_no_block_alive(ds):
    """A kill mid-epoch drops the staged batches without a release: their
    blocks are collected, and the next epoch trains on."""
    held = []

    class Tracking(PoisonedBlockPool):
        def acquire(self, shape, dtype):
            block, reused = super().acquire(shape, dtype)
            held.append(weakref.ref(block))
            return block, reused

    from repro.faults import FaultInjected

    inj = FaultInjector(schedule=[FaultAction("kill", epoch=0, batch=2)])
    pool = Tracking()
    tr = _trainer(ds, pool, inj, plan_source="pipelined")
    with pytest.raises(FaultInjected):
        tr.train_epoch(max_iters=4)
    gc.collect()
    free = {id(b) for blocks in pool._free.values() for b in blocks}
    assert all(r() is None or id(r()) in free for r in held)
    st = tr.train_epoch(max_iters=2)
    assert np.isfinite(st.totals()["loss"])


@pytest.mark.parametrize(
    "source,replicas",
    [("serial", 0), ("pipelined", 0), ("pipelined", 2), ("inline", 0),
     ("inline", 2)],
)
def test_blocks_are_released_only_between_steps(ds, source, replicas):
    """Every block the pool takes back is released while no step is in
    flight: each dispatched step has synced, so the device has read its
    inputs (and ``jnp.asarray`` may have aliased them on the CPU)."""
    steps = {"dispatched": 0, "synced": 0}
    early = []

    class Checking(PoisonedBlockPool):
        def release(self, block):
            if steps["dispatched"] != steps["synced"]:
                early.append(dict(steps))
            return super().release(block)

    pool = Checking()
    tr = _trainer(ds, pool, plan_source="serial" if source == "inline" else source,
                  num_devices=2, num_replicas=replicas)
    dispatch, sync = tr._dispatch_step, tr._sync_step

    def counting_dispatch(*args):
        steps["dispatched"] += 1
        return dispatch(*args)

    def counting_sync(*args):
        out = sync(*args)
        steps["synced"] += 1
        return out

    tr._dispatch_step, tr._sync_step = counting_dispatch, counting_sync
    if source == "inline":
        for epoch in range(2):
            for targets in tr.sampler.epoch_targets(epoch):
                tr.train_iter(targets)
    else:
        _losses(tr)
    assert steps["dispatched"] == steps["synced"] > 0
    assert pool.reused > 0 and early == []
