"""Pipelined runtime: ordered bounded prefetch, clean shutdown, and
serial-equals-pipelined determinism across all three parallelism modes."""
import threading
import time

import numpy as np
import pytest

from repro.graph.datasets import make_dataset
from repro.models.gnn import GNNSpec
from repro.runtime import OrderedPrefetcher, plan_signature
from repro.testing import PoisonedBlockPool
from repro.train.trainer import TrainConfig, Trainer


@pytest.fixture(scope="module")
def ds():
    return make_dataset("tiny")


def _spec(ds):
    return GNNSpec(
        model="sage", in_dim=ds.spec.feat_dim, hidden_dim=16,
        out_dim=ds.spec.num_classes, num_layers=2, num_heads=4,
    )


def _trajectory(ds, mode, source, epochs=2, iters=3, pool="default"):
    """``pool``: the trainer's own by default; None gathers each batch into
    a fresh array; else the feature-block pool to install."""
    cfg = TrainConfig(
        mode=mode, num_devices=4, fanouts=(4, 4), batch_size=32,
        presample_epochs=2, plan_source=source, pipeline_depth=3,
        plan_workers=2, seed=7,
    )
    tr = Trainer(ds, _spec(ds), cfg)
    if pool != "default":
        tr.producer.pool = pool
        if pool is not None:
            tr.feature_pool = pool
    traj = []
    last = None
    for _ in range(epochs):
        last = tr.train_epoch(max_iters=iters)
        traj += [(i.loss, i.accuracy) for i in last.iters]
    return tr, traj, last


# --------------------------------------------------------------------- #
# prefetcher semantics
# --------------------------------------------------------------------- #
def test_prefetcher_delivers_in_order_with_bounded_lookahead():
    in_flight = []
    lock = threading.Lock()
    peak = [0]

    def fn(i):
        with lock:
            in_flight.append(i)
            peak[0] = max(peak[0], len(in_flight))
        time.sleep(0.002 * ((i * 7) % 3))  # jitter completion order
        with lock:
            in_flight.remove(i)
        return i * i

    pf = OrderedPrefetcher(fn, 20, depth=3, workers=4)
    assert list(pf) == [i * i for i in range(20)]
    assert peak[0] <= 3  # never more than `depth` claimed at once
    assert pf.closed
    assert pf.stats.delivered == 20


def test_prefetcher_raises_at_failing_index_and_shuts_down():
    seen = []

    def fn(i):
        if i == 2:
            raise ValueError("boom at 2")
        return i

    pf = OrderedPrefetcher(fn, 6, depth=2, workers=2)
    it = iter(pf)
    seen.append(next(it))
    seen.append(next(it))
    with pytest.raises(ValueError, match="boom at 2"):
        next(it)
    assert seen == [0, 1]
    assert pf.closed  # generator finally-block joined the workers


def test_prefetcher_stats_under_out_of_order_completion():
    """Occupancy accounting with a hand-scheduled reverse-order producer.

    Four gated workers claim items 0..3; releasing them 3,2,1,0 fills the
    reorder buffer completely before item 0 (the only deliverable one)
    lands. Delivery then drains the buffer 4->3->2->1, so the stats are
    exact: occupancy max 4, mean 2.5, no consumer wait once full.
    """
    gates = [threading.Event() for _ in range(4)]

    def fn(i):
        gates[i].wait(timeout=10.0)
        return i

    pf = OrderedPrefetcher(fn, 4, depth=4, workers=4)
    try:
        for i in (3, 2, 1, 0):  # complete in reverse delivery order
            gates[i].set()
        # wait until every item has been posted to the reorder buffer
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with pf._lock:
                if len(pf._buffer) == 4:
                    break
            time.sleep(0.001)
        assert list(pf) == [0, 1, 2, 3]  # order restored despite completion
    finally:
        pf.close()
    assert pf.stats.delivered == 4
    assert pf.stats.occupancy_max == 4  # the buffer really held all 4
    assert pf.stats.mean_occupancy == pytest.approx(2.5)  # (4+3+2+1)/4
    assert pf.stats.consumer_waits == 0  # everything was ready up front
    assert pf.stats.as_dict()["max_occupancy"] == 4


def test_prefetcher_counts_consumer_waits_when_producer_lags():
    """Each delivery blocks until the matching gate opens, so every one of
    the four deliveries is a counted consumer wait."""
    gates = [threading.Event() for _ in range(4)]

    def fn(i):
        gates[i].wait(timeout=10.0)
        return i

    pf = OrderedPrefetcher(fn, 4, depth=4, workers=4)
    got = []

    def consume():
        got.extend(pf)

    t = threading.Thread(target=consume)
    t.start()
    try:
        for i in range(4):
            # release item i only after the consumer is provably blocked
            deadline = time.monotonic() + 10.0
            while pf.stats.consumer_waits < i + 1:
                assert time.monotonic() < deadline, "consumer never blocked"
                time.sleep(0.001)
            gates[i].set()
        t.join(timeout=10.0)
    finally:
        for g in gates:
            g.set()
        pf.close()
    assert got == [0, 1, 2, 3]
    assert pf.stats.consumer_waits == 4  # every delivery blocked
    assert pf.stats.occupancy_max == 1  # nothing ever queued ahead
    assert pf.stats.mean_occupancy == pytest.approx(1.0)


def test_prefetcher_close_midstream_joins_workers():
    def fn(i):
        time.sleep(0.001)
        return i

    pf = OrderedPrefetcher(fn, 50, depth=4, workers=3)
    it = iter(pf)
    assert next(it) == 0
    it.close()  # consumer abandons the epoch
    assert pf.closed


# --------------------------------------------------------------------- #
# determinism: pipelined == serial, bit for bit
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("mode", ["split", "dp", "pushpull"])
def test_pipelined_matches_serial_trajectory(ds, mode):
    # the serial side gathers into fresh arrays; the pipelined side reuses
    # pooled blocks that are NaN-poisoned on release and alias their staged
    # device arrays (PoisonedBlockPool): a block released too early, or a
    # row left unwritten, would show in the losses
    _, serial, _ = _trajectory(ds, mode, "serial", pool=None)
    pool = PoisonedBlockPool()
    _, pipelined, _ = _trajectory(ds, mode, "pipelined", pool=pool)
    assert len(serial) == len(pipelined) > 0
    assert pool.reused > 0
    # exact float equality: same RNG keys, same padded shapes, same jit
    assert serial == pipelined


def test_keyed_sampler_is_order_independent(ds):
    from repro.graph.sampling import NeighborSampler

    s = NeighborSampler(ds.graph, ds.train_ids, [4, 4], 32, seed=5)
    batches = s.epoch_targets(0)
    a = s.sample_batch(batches[0], epoch=0, batch=0)
    s.sample_batch(batches[-1], epoch=0, batch=len(batches) - 1)  # interleave
    b = s.sample_batch(batches[0], epoch=0, batch=0)
    for la, lb in zip(a.layers, b.layers):
        np.testing.assert_array_equal(la.src, lb.src)
        np.testing.assert_array_equal(la.dst, lb.dst)
    c = s.sample_batch(batches[0], epoch=1, batch=0)
    assert any(
        la.src.shape != lc.src.shape or not np.array_equal(la.src, lc.src)
        for la, lc in zip(a.layers, c.layers)
    )


# --------------------------------------------------------------------- #
# signature cache + queue stats
# --------------------------------------------------------------------- #
def test_signature_cache_converges(ds):
    tr, _, last = _trajectory(ds, "split", "pipelined", epochs=3, iters=3)
    assert tr.sig_cache.hits > 0
    assert tr.sig_cache.hit_rate > 0.5  # steady state reuses signatures
    # HWM repad bounds the number of distinct compiled signatures
    assert tr.sig_cache.num_signatures <= 3
    assert last.pipeline["delivered"] > 0
    assert "mean_occupancy" in last.pipeline
    assert last.pipeline["hit_rate"] == tr.sig_cache.hit_rate


def test_plan_signature_tracks_padded_shapes(ds):
    tr, _, _ = _trajectory(ds, "split", "serial", epochs=1, iters=2)
    src = tr.plan_source_for(99, max_iters=1)
    batch = next(iter(src))
    # delivered signatures fold in the static overlap-schedule knobs
    # (wire_dtype, chunks, overlap) — they retrace the step without
    # changing any array shape (DESIGN.md §3a)
    extra = (tr.cfg.wire_dtype, tr.cfg.shuffle_chunks, tr.cfg.shuffle_overlap)
    sig = plan_signature(batch.plan, extra=extra)
    assert sig == batch.signature
    assert sig != plan_signature(batch.plan, extra=("bfloat16", 4, True))
    assert sig[0] == 4 and sig[1] == 2  # (P, L, fronts, layers, cache, extra)


def test_pipelined_producer_failure_propagates_and_cleans_up(ds):
    cfg = TrainConfig(
        mode="split", num_devices=4, fanouts=(4, 4), batch_size=32,
        presample_epochs=1, plan_source="pipelined", plan_workers=2,
    )
    tr = Trainer(ds, _spec(ds), cfg)
    orig = tr.producer.build

    def failing(epoch, index, targets):
        if index >= 1:
            raise RuntimeError("producer died")
        return orig(epoch, index, targets)

    tr.producer.build = failing
    with pytest.raises(RuntimeError, match="producer died"):
        tr.train_epoch(max_iters=3)
    # a fresh epoch with the healed producer still works (no stuck threads)
    tr.producer.build = orig
    st = tr.train_epoch(max_iters=2)
    assert len(st.iters) > 0 and np.isfinite(st.totals()["loss"])


# --------------------------------------------------------------------- #
# recompile tracing: steady state at fixed caps is zero jit cache misses
# --------------------------------------------------------------------- #
@pytest.mark.parametrize(
    "source", ["serial", "pipelined", "device", "device_pipelined"]
)
def test_no_steady_state_recompiles(ds, source):
    cfg = TrainConfig(
        mode="split", num_devices=4, fanouts=(4, 4), batch_size=32,
        presample_epochs=2, plan_source=source, pipeline_depth=3,
        plan_workers=2, sampler_backend="jnp", trace_recompiles=True, seed=7,
    )
    tr = Trainer(ds, _spec(ds), cfg)
    last = None
    for _ in range(4):  # HWM caps only grow; they settle within warmup
        last = tr.train_epoch(max_iters=3)
    assert last.recompiles["steps"] == len(last.iters) > 0
    # the steady-state contract: high-water-mark repadding + signature-keyed
    # delivery means a warm epoch at fixed caps never retraces
    assert last.recompiles["misses"] == 0, last.recompiles
    # and the probe is live, not vacuously zero: warmup paid compiles
    assert tr.recompiles.total_misses > 0
