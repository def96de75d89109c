"""Plan sources: who builds the per-iteration ``SplitPlan`` and when.

GSplit's cooperative pipeline (paper §5) overlaps the host-side stages of
mini-batch ``k+1`` (sampling, online splitting, feature loading) with the
device compute of mini-batch ``k``. This module factors the host side out of
the trainer behind one interface:

  * ``SerialPlanSource``     -- build each batch inline on the consumer
    thread, exactly like the pre-pipeline trainer. The reference for
    determinism tests.
  * ``PipelinedPlanSource``  -- a multi-worker producer pool builds batches
    ahead of the consumer through ``OrderedPrefetcher``; a bounded reorder
    queue keeps delivery in epoch order.
  * ``DevicePlanSource`` / ``DevicePipelinedPlanSource`` -- the same two
    delivery disciplines with the *sampling* stage running on device
    (``repro.sampler``, docs/SAMPLER.md): the producer hands targets to the
    cooperative sampling engine and assembles the returned frontier/edge
    blocks into the standard ``SplitPlan``, so repadding, signatures, and
    the trainer are untouched. Device-mode capacity growth is applied at
    source creation (epoch boundary) — never mid-epoch — which keeps the
    serial == pipelined contract intact for device sampling too.

Both sources derive one RNG stream *per batch* from ``(seed, epoch, index)``
(see ``NeighborSampler.sample_batch``), so their sampled batches are
identical regardless of which thread runs the sampler. Padding to the
running high-water marks (``repad_plan``) is applied at *delivery* time, on
the ordered side of the queue, so padded shapes — and therefore jit
signatures and float trajectories — are bit-for-bit identical between the
two sources.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Iterator

import numpy as np

from repro.core.splitting import (
    SplitPlan,
    build_dp_plan,
    build_split_plan,
    pad_axis,
    repad_plan,
)
from repro.faults.retry import RetryPolicy
from repro.graph.cache import CachePlan, FeatureCache, LoadBreakdown
from repro.graph.sampling import NeighborSampler
from repro.obs import NULL_OBS, Obs, note_hwm_growth
from repro.runtime.prefetch import OrderedPrefetcher, current_worker
from repro.runtime.signature import SignatureCache, mesh_signature, plan_signature

# NOTE: repro.train.plan_io is imported lazily inside PlanProducer.build —
# repro.train's package __init__ imports the trainer, which imports this
# package, so a module-level import here would be circular.


@dataclass
class PlanBatch:
    """One fully-staged mini-batch: plan + host feature/label blocks.

    With a ``cache_plan``, ``feats`` is the compacted (P, M, F) cache-miss
    block; without one it is the full (P, N_L, F) host gather.
    """

    index: int
    epoch: int
    plan: SplitPlan
    feats: np.ndarray  # (P, N_L, F) — or (P, M, F) misses when cache-served
    labels: np.ndarray  # (P, N_0) int32, padding zeroed
    breakdown: LoadBreakdown | None
    t_sample: float
    t_split: float
    t_load: float
    cache_plan: CachePlan | None = None
    signature: tuple = ()
    sig_hit: bool = False
    # producer-side completion time (perf_counter): delivery minus this is
    # the prefetch-queue dwell, exported as the ``plan/queue_dwell`` span
    t_built: float = 0.0


@dataclass
class MeshPlanBatch:
    """One global mini-batch fanned out across the replica axis.

    ``parts[r]`` is replica ``r``'s fully-staged ``PlanBatch`` (its own
    sampled subgraph, split plan, feature/label blocks) over the same P-way
    partition; the mesh step consumes all R parts in one jitted call and
    averages the gradients across the replica axis (DESIGN.md §9). Stage
    timings are summed over parts — the host cost of one global batch.
    """

    index: int
    epoch: int
    parts: list  # R PlanBatch, replica order
    t_sample: float = 0.0
    t_split: float = 0.0
    t_load: float = 0.0
    signature: tuple = ()
    sig_hit: bool = False
    t_built: float = 0.0

    @property
    def num_replicas(self) -> int:
        return len(self.parts)


def _load_counters(span, plan, cache_plan, features: np.ndarray,
                   reused: bool) -> None:
    """``plan/load``'s ``rows`` (the true rows gathered), ``bytes`` (what
    they read from the feature table) and ``reused`` (1 when the block they
    were written into came from the pool, 0 when it was allocated)."""
    from repro.train.plan_io import true_feature_rows

    rows = true_feature_rows(plan, cache_plan)
    span.set(rows=rows, bytes=rows * features.shape[1] * features.dtype.itemsize,
             reused=int(reused))


class PlanProducer:
    """Builds one ``PlanBatch``: sample -> online split -> feature load.

    Stateless across batches apart from read-only references (graph, feature
    matrix, partition assignment, cache tables), so any thread may build any
    batch. High-water-mark repadding is deliberately *not* done here — it is
    order-sensitive and belongs on the ordered side of the queue
    (``_finalize``).
    """

    def __init__(
        self,
        sampler: NeighborSampler,
        features: np.ndarray,
        labels: np.ndarray,
        mode: str,
        num_devices: int,
        pad_multiple: int,
        assignment: np.ndarray | None = None,
        cache: FeatureCache | None = None,
        serve_cache: bool = True,
        device_sampler=None,  # repro.sampler.DeviceSampler | None
        with_halves: bool = False,  # build the §3a local/remote edge halves
        replication=None,  # core.partition.ReplicationSet | None
        telemetry=None,  # core.partition.EdgeTelemetry | None
        num_replicas: int = 0,  # 0 = 1D path; >=1 = (R, P) mesh fan-out
        obs: Obs = NULL_OBS,  # tracing/metrics sink (repro.obs)
        injector=None,  # repro.faults.FaultInjector | None (chaos hooks)
        pool=None,  # train.plan_io.FeatureBlockPool | None (feature blocks)
    ):
        if mode not in ("split", "dp", "pushpull"):
            raise ValueError(f"unknown mode {mode!r}")
        if mode == "split" and assignment is None:
            raise ValueError("split mode needs a partition assignment")
        if device_sampler is not None and mode != "split":
            raise ValueError("device sampling is split-mode only")
        if num_replicas < 0:
            raise ValueError(f"num_replicas must be >= 0, got {num_replicas}")
        if num_replicas >= 1 and mode != "split":
            raise ValueError("the (R, P) mesh composes with mode='split' only")
        self.sampler = sampler
        self.features = features
        self.labels = labels
        self.mode = mode
        self.num_devices = num_devices
        self.pad_multiple = pad_multiple
        self.assignment = assignment
        self.cache = cache
        self.serve_cache = serve_cache
        self.device_sampler = device_sampler
        self.with_halves = with_halves
        if replication is not None and mode != "split":
            raise ValueError("hot-vertex replication is split-mode only")
        # mutable on purpose: Trainer.refine_partition swaps both between
        # epochs; EdgeTelemetry.record is thread-safe for pipelined producers
        self.replication = replication
        self.telemetry = telemetry
        self.num_replicas = num_replicas
        self.obs = obs
        self.injector = injector
        # the feature blocks are written into blocks from this pool; the
        # consumer hands each back once its step has read it (None: a fresh
        # array per batch)
        self.pool = pool

    def build(self, epoch: int, index: int, targets: np.ndarray):
        from repro.train.plan_io import load_labels, stage_host_features

        if self.injector is not None:
            # deterministic chaos hook (repro.faults.inject): raises the
            # scheduled fault / sleeps the scheduled delay, or no-ops
            self.injector.fire("build", epoch, index)
        if self.num_replicas >= 1:
            return self._build_mesh(epoch, index, targets)
        obs = self.obs
        with obs.span("plan/build",
                      {"epoch": epoch, "batch": index, "worker": current_worker()},
                      cpu=True):
            with obs.span("plan/sample", cpu=True) as sp_sample:
                if self.mode in ("dp", "pushpull"):
                    samples = self.sampler.sample_micro_batch(
                        targets, self.num_devices, epoch, index
                    )
                else:
                    # device mode: the cooperative engine samples
                    # on-accelerator and falls back to the host sampler's
                    # keyed API on cap overflow — both are pure functions
                    # of (seed, epoch, index)
                    if self.device_sampler is not None:
                        sample = self.device_sampler.sample_batch(
                            targets, epoch, index
                        )
                    else:
                        sample = self.sampler.sample_batch(targets, epoch, index)
            with obs.span("plan/split", cpu=True) as sp_split:
                if self.mode in ("dp", "pushpull"):
                    plan = build_dp_plan(
                        samples, pad_multiple=self.pad_multiple,
                        with_halves=self.with_halves,
                    )
                else:
                    if self.telemetry is not None:
                        self.telemetry.record(sample)
                    plan = build_split_plan(
                        sample,
                        self.assignment,
                        self.num_devices,
                        pad_multiple=self.pad_multiple,
                        with_halves=self.with_halves,
                        replication=self.replication,
                    )
            with obs.span("plan/load", cpu=True) as sp_load:
                cache_plan, feats, breakdown, reused = stage_host_features(
                    plan, self.features, self.cache, self.serve_cache,
                    self.pad_multiple, self.pool,
                )
                labels = load_labels(plan, self.labels)
                if obs.enabled:
                    _load_counters(sp_load, plan, cache_plan, self.features,
                                   reused)
            if self.injector is not None:
                feats = self.injector.maybe_poison("build", epoch, index, feats)
            # the producer end of the flow arrow that lands on the consumer
            # step training on this plan (keyed by the plan's (epoch, batch))
            obs.flow_start(("plan", epoch, index))
        return PlanBatch(
            index=index,
            epoch=epoch,
            plan=plan,
            feats=feats,
            labels=labels,
            breakdown=breakdown,
            t_sample=sp_sample.duration,
            t_split=sp_split.duration,
            t_load=sp_load.duration,
            cache_plan=cache_plan,
            t_built=time.perf_counter(),
        )

    def _sample_replicas(self, epoch: int, index: int, targets: np.ndarray):
        """The R per-replica samples for one global batch, in replica order.

        R == 1 uses the *unsuffixed* batch key — the exact draw the 1D
        producer makes — so the degenerate mesh is bit-identical to the 1D
        path. R > 1 keys host draws like ``sample_micro_batch`` (chunk r
        gets ``(0x5A3, epoch, index, r)``), which makes an R×1 mesh sample
        exactly the micro-batches a ``dp`` run over R devices would; the
        device engine folds ``(replica, R)`` into its flattened batch
        counter instead (see ``DeviceSampler.sample_batch``).
        """
        R = self.num_replicas
        if R == 1:
            if self.device_sampler is not None:
                return [self.device_sampler.sample_batch(targets, epoch, index)]
            return [self.sampler.sample_batch(targets, epoch, index)]
        if self.device_sampler is not None:
            chunks = np.array_split(targets, R)
            return [
                self.device_sampler.sample_batch(
                    chunk, epoch, index, replica=r, num_replicas=R
                )
                for r, chunk in enumerate(chunks)
            ]
        return self.sampler.sample_micro_batch(targets, R, epoch, index)

    def _build_mesh(
        self, epoch: int, index: int, targets: np.ndarray
    ) -> MeshPlanBatch:
        """Fan one global batch out across the replica axis (mesh mode).

        Each replica's chunk of ``targets`` is sampled independently (keyed
        RNG — see ``_sample_replicas``) and goes through the same online
        split -> feature load stages as the 1D path, over the *same* P-way
        partition/cache/replication tables (shared read-only state: the
        graph is partitioned once, every replica group maps vertex -> split
        identically). High-water-mark repadding stays on the delivery side
        (``_finalize``), which also makes the R parts rectangular.
        """
        from repro.train.plan_io import load_labels, stage_host_features

        obs = self.obs
        with obs.span("plan/build",
                      {"epoch": epoch, "batch": index, "worker": current_worker()},
                      cpu=True):
            with obs.span("plan/sample", cpu=True) as sp_sample:
                samples = self._sample_replicas(epoch, index, targets)
            parts, t_split, t_load = [], 0.0, 0.0
            for replica, sample in enumerate(samples):
                with obs.span("plan/split", {"replica": replica},
                              cpu=True) as sp_split:
                    if self.telemetry is not None:
                        self.telemetry.record(sample)
                    plan = build_split_plan(
                        sample,
                        self.assignment,
                        self.num_devices,
                        pad_multiple=self.pad_multiple,
                        with_halves=self.with_halves,
                        replication=self.replication,
                    )
                with obs.span("plan/load", {"replica": replica},
                              cpu=True) as sp_load:
                    cache_plan, feats, breakdown, reused = stage_host_features(
                        plan, self.features, self.cache, self.serve_cache,
                        self.pad_multiple, self.pool,
                    )
                    labels = load_labels(plan, self.labels)
                    if obs.enabled:
                        _load_counters(sp_load, plan, cache_plan,
                                       self.features, reused)
                if self.injector is not None:
                    # _take claims once, so at most one replica is poisoned
                    feats = self.injector.maybe_poison(
                        "build", epoch, index, feats
                    )
                t_split += sp_split.duration
                t_load += sp_load.duration
                parts.append(
                    PlanBatch(
                        index=index,
                        epoch=epoch,
                        plan=plan,
                        feats=feats,
                        labels=labels,
                        breakdown=breakdown,
                        t_sample=0.0,
                        t_split=sp_split.duration,
                        t_load=sp_load.duration,
                        cache_plan=cache_plan,
                    )
                )
            obs.flow_start(("plan", epoch, index))
        return MeshPlanBatch(
            index=index,
            epoch=epoch,
            parts=parts,
            t_sample=sp_sample.duration,
            t_split=t_split,
            t_load=t_load,
            t_built=time.perf_counter(),
        )


def finalize_cache_plan(cp: CachePlan, hwm: dict, n_l: int) -> CachePlan:
    """Grow a cache plan to the running high-water marks (``CM``/``CS``).

    The single definition of the cache-plan HWM keys — shared by the
    delivery-side ``_finalize`` and the trainer's inline ``train_iter`` path
    so the two stay bit-identical.
    """
    hwm["CM"] = max(hwm.get("CM", 0), cp.max_miss)
    hwm["CS"] = max(hwm.get("CS", 0), cp.max_send)
    return cp.pad_to(n_l, hwm["CM"], hwm["CS"])


def _repad_blocks(part: PlanBatch, hwm: dict, pool) -> None:
    """Pad a part's feature and label blocks to its repadded plan (and cache
    plan); ``pool`` takes back a feature block that the repad replaces."""
    from repro.train.plan_io import pad_block

    if part.cache_plan is not None:
        rows = hwm["CM"]
    else:
        rows = part.plan.front_ids[-1].shape[1]
    part.feats = pad_block(part.feats, rows, pool)
    part.labels = pad_axis(part.labels, 1, part.plan.front_ids[0].shape[1])


def _repad_counters(span, parts: list) -> None:
    """``plan/repad``'s ``rows`` (true rows of the staged feature blocks)
    and ``rows_padded`` (their padded height, over devices and parts)."""
    from repro.train.plan_io import true_feature_rows

    span.set(
        rows=sum(true_feature_rows(p.plan, p.cache_plan) for p in parts),
        rows_padded=sum(p.feats.shape[0] * p.feats.shape[1] for p in parts),
    )


def _finalize_mesh(
    batch: MeshPlanBatch,
    hwm: dict,
    sig_cache: SignatureCache | None,
    sig_extra: tuple = (),
    obs: Obs = NULL_OBS,
    pool=None,
) -> MeshPlanBatch:
    """Delivery-side finalize for a mesh batch: two repad passes over the R
    parts against the *shared* high-water marks.

    Pass 1 absorbs every part's widths into ``hwm`` (replica order — the
    same order-sensitivity contract as the 1D path, which is why this runs
    on the ordered side of the queue); pass 2 repads each part against the
    settled marks, so all R parts leave with identical padded shapes —
    rectangular across the replica axis, ready to stack for spmd. Repadding
    only ever grows to the marks (``pad_axis`` is a no-op at width), so the
    second pass is idempotent; with R == 1 it is a literal no-op and the
    part is processed exactly like the 1D ``_finalize``. One mesh signature
    (keyed on the mesh shape, ``mesh_signature``) is recorded per delivery
    — the mesh step is one executable, so one cache entry is the honest
    unit.
    """
    if batch.t_built:
        obs.record("plan/queue_dwell", batch.t_built, time.perf_counter(),
                   {"epoch": batch.epoch, "batch": batch.index})
    before = dict(hwm)
    with obs.span("plan/repad", {"epoch": batch.epoch, "batch": batch.index}) as sp:
        for _ in range(2):
            for part in batch.parts:
                repad_plan(part.plan, hwm)
                if part.cache_plan is not None:
                    finalize_cache_plan(
                        part.cache_plan, hwm, part.plan.front_ids[-1].shape[1]
                    )
        for part in batch.parts:
            _repad_blocks(part, hwm, pool)
        if obs.enabled:
            _repad_counters(sp, batch.parts)
    note_hwm_growth(obs, before, hwm, f"epoch{batch.epoch}/batch{batch.index}")
    batch.t_split += sp.duration
    batch.signature = mesh_signature(
        [(p.plan, p.cache_plan) for p in batch.parts], sig_extra
    )
    if sig_cache is not None:
        batch.sig_hit = sig_cache.record(batch.signature)
        obs.count("sig/hit" if batch.sig_hit else "sig/miss")
    return batch


def _finalize(
    batch: PlanBatch,
    hwm: dict,
    sig_cache: SignatureCache | None,
    sig_extra: tuple = (),
    obs: Obs = NULL_OBS,
    pool=None,
) -> PlanBatch:
    """Order-sensitive delivery step: repad to high-water marks, pad the
    staged feature/label blocks to match, and record the jit signature.

    The cache plan is repadded here too (keys ``CM``/``CS``): its arrays are
    purely position-based, so growing them only appends masked entries —
    unlike ``edge_src``, nothing needs rebasing. Mesh batches take the
    two-pass variant above. Observability rides the delivery point: the
    queue-dwell span (producer completion -> here), the repad span, any
    high-water-mark growth (a retrace warning — see ``note_hwm_growth``),
    and the signature hit/miss counters. ``pool`` takes back a pooled
    feature block that the repad replaces with a larger copy.
    """
    if isinstance(batch, MeshPlanBatch):
        return _finalize_mesh(batch, hwm, sig_cache, sig_extra, obs, pool)
    if batch.t_built:
        obs.record("plan/queue_dwell", batch.t_built, time.perf_counter(),
                   {"epoch": batch.epoch, "batch": batch.index})
    before = dict(hwm)
    with obs.span("plan/repad", {"epoch": batch.epoch, "batch": batch.index}) as sp:
        repad_plan(batch.plan, hwm)
        if batch.cache_plan is not None:
            finalize_cache_plan(
                batch.cache_plan, hwm, batch.plan.front_ids[-1].shape[1]
            )
        _repad_blocks(batch, hwm, pool)
        if obs.enabled:
            _repad_counters(sp, [batch])
    note_hwm_growth(obs, before, hwm, f"epoch{batch.epoch}/batch{batch.index}")
    batch.t_split += sp.duration
    batch.signature = plan_signature(batch.plan, batch.cache_plan, sig_extra)
    if sig_cache is not None:
        batch.sig_hit = sig_cache.record(batch.signature)
        obs.count("sig/hit" if batch.sig_hit else "sig/miss")
    return batch


class PlanSource:
    """Iterable of ``PlanBatch`` for one epoch. Subclasses choose *where*
    the producer work runs; delivery order and contents are identical."""

    def __iter__(self) -> Iterator[PlanBatch]:
        raise NotImplementedError

    def close(self) -> None:  # pragma: no cover - overridden when stateful
        pass

    def stats(self) -> dict:
        return {}

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        self.close()


@dataclass
class SerialPlanSource(PlanSource):
    """Inline plan construction on the consumer thread (today's behavior)."""

    producer: PlanProducer
    epoch: int
    batches: list
    hwm: dict
    sig_cache: SignatureCache | None = None
    # static program-structure key (wire_dtype, chunks, overlap) folded into
    # every delivered signature — see ``plan_signature``
    sig_extra: tuple = ()
    obs: Obs = NULL_OBS
    # first batch's *global* epoch index: a mid-epoch resume slices
    # ``batches`` to the tail but must key each build by its original
    # (epoch, index) coordinate so the keyed RNG reproduces the exact
    # draws an uninterrupted run would make (docs/ROBUSTNESS.md)
    start: int = 0

    def __iter__(self) -> Iterator[PlanBatch]:
        for idx, targets in enumerate(self.batches):
            yield _finalize(
                self.producer.build(self.epoch, idx + self.start, targets),
                self.hwm,
                self.sig_cache,
                self.sig_extra,
                self.obs,
                self.producer.pool,
            )

    def stats(self) -> dict:
        return dict(self.sig_cache.as_dict()) if self.sig_cache else {}


@dataclass
class PipelinedPlanSource(PlanSource):
    """Multi-worker lookahead plan construction behind a bounded queue."""

    producer: PlanProducer
    epoch: int
    batches: list
    hwm: dict
    sig_cache: SignatureCache | None = None
    sig_extra: tuple = ()
    obs: Obs = NULL_OBS
    start: int = 0  # global index of batches[0] (see SerialPlanSource)
    depth: int = 4
    workers: int = 2
    # producer supervision (docs/ROBUSTNESS.md): transient-build retry
    # budget and the consumer-side stall watchdog, both forwarded to
    # OrderedPrefetcher
    retry: RetryPolicy | None = None
    stall_timeout_s: float | None = None
    _prefetcher: OrderedPrefetcher | None = field(
        default=None, repr=False, compare=False
    )

    def __iter__(self) -> Iterator[PlanBatch]:
        batches = list(self.batches)

        def build(idx: int) -> PlanBatch:
            return self.producer.build(self.epoch, idx + self.start, batches[idx])

        self._prefetcher = OrderedPrefetcher(
            build,
            len(batches),
            depth=self.depth,
            workers=self.workers,
            retry=self.retry,
            stall_timeout_s=self.stall_timeout_s,
            obs=self.obs,
        )
        try:
            for batch in self._prefetcher:
                yield _finalize(
                    batch, self.hwm, self.sig_cache, self.sig_extra, self.obs,
                    self.producer.pool,
                )
        finally:
            self.close()

    def close(self) -> None:
        if self._prefetcher is not None:
            self._prefetcher.close()

    def stats(self) -> dict:
        out = {}
        if self._prefetcher is not None:
            out.update(self._prefetcher.stats.as_dict())
        if self.sig_cache is not None:
            out.update(self.sig_cache.as_dict())
        return out


class _DeviceSourceMixin:
    """Shared device-mode discipline for both delivery flavors.

    Capacity high-water-mark growth is applied exactly once, when iteration
    starts (the epoch boundary): within the epoch every producer thread sees
    one frozen capacity table, so which batches overflow — and fall back to
    the host sampler — is reproducible and delivery-order independent.
    """

    def _device_sampler(self):
        eng = self.producer.device_sampler
        if eng is None:
            raise ValueError(
                "device plan source needs a PlanProducer with a device_sampler"
            )
        return eng

    def stats(self) -> dict:
        out = super().stats()
        out.update(self._device_sampler().stats())
        return out


@dataclass
class DevicePlanSource(_DeviceSourceMixin, SerialPlanSource):
    """Inline delivery; sampling runs on the device engine."""

    def __iter__(self) -> Iterator[PlanBatch]:
        self._device_sampler().refresh_caps()
        yield from SerialPlanSource.__iter__(self)


@dataclass
class DevicePipelinedPlanSource(_DeviceSourceMixin, PipelinedPlanSource):
    """Pipelined delivery; producer threads share the jitted device engine."""

    def __iter__(self) -> Iterator[PlanBatch]:
        self._device_sampler().refresh_caps()
        yield from PipelinedPlanSource.__iter__(self)


def make_plan_source(
    kind: str,
    producer: PlanProducer,
    epoch: int,
    batches: list,
    hwm: dict,
    sig_cache: SignatureCache | None = None,
    depth: int = 4,
    workers: int = 2,
    sig_extra: tuple = (),
    obs: Obs = NULL_OBS,
    start: int = 0,
    retry: RetryPolicy | None = None,
    stall_timeout_s: float | None = None,
) -> PlanSource:
    if kind == "serial":
        return SerialPlanSource(
            producer, epoch, batches, hwm, sig_cache, sig_extra, obs, start
        )
    if kind == "pipelined":
        return PipelinedPlanSource(
            producer, epoch, batches, hwm, sig_cache, sig_extra, obs, start,
            depth, workers, retry, stall_timeout_s,
        )
    if kind == "device":
        return DevicePlanSource(
            producer, epoch, batches, hwm, sig_cache, sig_extra, obs, start
        )
    if kind == "device_pipelined":
        return DevicePipelinedPlanSource(
            producer, epoch, batches, hwm, sig_cache, sig_extra, obs, start,
            depth, workers, retry, stall_timeout_s,
        )
    raise ValueError(
        f"unknown plan source {kind!r} "
        "(serial | pipelined | device | device_pipelined)"
    )
