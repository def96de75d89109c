"""Training runtime: one trainer, three parallelism paradigms.

  * ``split``     -- the paper's split parallelism: one mini-batch, split
                     online by f_G, per-layer all-to-all shuffles.
  * ``dp``        -- data parallelism (DGL/Quiver baseline): one micro-batch
                     per device, redundant loads + compute, no shuffles.
  * ``pushpull``  -- P3* hybrid: bottom layer model-parallel over feature
                     slices + per-micro push-pull of partial activations,
                     upper layers data-parallel. On this CPU container the
                     numerics equal ``dp`` (the slice-sum is exact); the
                     *communication/compute accounting* follows P3 and feeds
                     the epoch-time model (benchmarks/epoch_time.py).

All modes share one jitted step (single-device "sim" execution with a leading
device axis P); the plan structure is the only thing that differs, mirroring
how GSplit's layer-centric API reuses single-GPU kernels (paper §6).
"""
from __future__ import annotations

import logging
import os
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.splitting import repad_plan
from repro.faults.retry import RetryPolicy
from repro.core import (
    build_dp_plan,
    build_split_plan,
    partition_graph,
    presample,
    sim_shuffle,
)
from repro.graph.cache import FeatureCache, LoadBreakdown
from repro.graph.datasets import GraphDataset
from repro.graph.sampling import NeighborSampler
from repro.models.gnn import GNNSpec, init_gnn_params
from repro.models.gnn.layers import gnn_forward, gnn_forward_cached
from repro.obs import NULL_OBS, Obs, note_hwm_growth
from repro.runtime import (
    MeshPlanBatch,
    PlanBatch,
    PlanProducer,
    SignatureCache,
    make_plan_source,
)
from repro.runtime.plan_source import finalize_cache_plan
from repro.train import optimizer as opt_lib
from repro.train.checkpoint import (
    checkpoint_name,
    load_latest_checkpoint,
    save_checkpoint as _save_checkpoint,
)
from repro.train.loss import masked_softmax_xent, masked_accuracy
from repro.train.plan_io import (
    FeatureBlockPool,
    load_labels,
    pad_block,
    plan_to_device,
    stage_batch,
    stage_host_features,
    transfer_counts,
)


@dataclass
class TrainConfig:
    mode: str = "split"  # split | dp | pushpull
    num_devices: int = 4
    fanouts: tuple[int, ...] = (15, 15, 15)
    batch_size: int = 1024
    lr: float = 1e-3
    optimizer: str = "adam"
    partition_method: str = "gsplit"  # split mode: gsplit | node | edge | rand
    presample_epochs: int = 10
    presample_workers: int = 1
    pad_multiple: int = -1  # -1 = pow2 bucketing
    cache_mode: str = "none"  # none | distributed | partitioned
    cache_capacity_per_device: int = 0
    cache_serve: bool = True  # serve hits from the device-resident block
    #   (False = legacy accounting-only cache: full host gather every step)
    # serial | pipelined (DESIGN.md §6) | device | device_pipelined — the
    # ``device*`` kinds run the sampling stage on the accelerator via the
    # cooperative engine (repro.sampler, docs/SAMPLER.md); split mode only.
    # The legacy inline path (``train_iter``) always samples on host.
    plan_source: str = "serial"
    pipeline_depth: int = 4  # max in-flight batches (pipelined source)
    plan_workers: int = 2  # producer threads (pipelined source)
    sampler_backend: str = "pallas"  # device sampling kernel: pallas | jnp
    # Overlap-aware shuffle schedule (DESIGN.md §3a). These are *execution*
    # knobs: the trainer copies them onto the model spec at init, so the
    # jitted step's layer shuffles and the cache remote fetch agree on one
    # wire format (the sampler's frontier exchange rides the same all-to-all
    # choke point but carries integer ids, which ``wire_cast`` exempts from
    # any down-cast). fp32 wire is bit-exact; bf16/fp16 quantize only bytes
    # on the wire (accumulation stays fp32).
    shuffle_overlap: bool = False  # split local/remote aggregation per layer
    shuffle_chunks: int = 1  # feature-axis tiles per layer all-to-all
    wire_dtype: str = "float32"  # float32 | bfloat16 | float16
    # Hot-vertex replication (DESIGN.md "Partitioning & replication"): a
    # fraction of feature memory spent on a device-resident block of the
    # hottest cross-part source vertices, replicated on every split. Edges
    # sourced at a replicated vertex are answered from the resident block
    # and never enter the all-to-all. Split mode only; 0.0 = off. dp /
    # pushpull plans are bit-identical regardless of this knob.
    replication_budget: float = 0.0  # fraction of |V| rows replicated
    # Record per-batch frontier/edge telemetry (core.partition.EdgeTelemetry)
    # from actual training batches; feed it back between epochs via
    # ``Trainer.refine_partition()`` (method="telemetry").
    record_telemetry: bool = False
    # Count jit cache misses per step (runtime.recompile.RecompileTracer);
    # per-epoch counts land in ``EpochStats.recompiles``. Steady state at
    # fixed caps must be zero — tests/test_runtime.py regresses this.
    trace_recompiles: bool = False
    # Unified tracing + metrics (repro.obs, DESIGN.md §10): record spans for
    # every host stage (producer build, queue dwell, repad, staging, the
    # device sync) flow-linked per (epoch, batch), plus the metrics registry
    # (signature/cache hit rates, wire bytes, HWM growth, recompiles,
    # prefetch occupancy). Off by default: the disabled path shares the
    # same code but records nothing and adds no host syncs (<1% step time,
    # gated by benchmarks/run.py obs_smoke).
    obs_trace: bool = False
    # When set (and obs_trace=True), ``train_epoch`` rewrites this path
    # with the cumulative Chrome trace (Perfetto-loadable; includes the
    # metrics snapshot) at every epoch end.
    obs_path: str | None = None
    # 2D (replica, split) mesh (DESIGN.md §9): 0 = the classic 1D P-way
    # split path (default); R >= 1 runs R replica groups of ``num_devices``
    # splits each — every global batch fans out into R independently
    # sampled per-replica plans over the *same* partition, the jitted mesh
    # step runs R split-local forward/backwards and averages gradients
    # across the replica axis. R = 1 is the degenerate mesh, pinned
    # bit-identical to the 1D path by tests/test_mesh.py. Split mode only.
    num_replicas: int = 0
    # ---- fault tolerance (repro.faults, docs/ROBUSTNESS.md) --------------
    # Crash-consistent checkpointing: with ckpt_dir set and ckpt_every > 0,
    # train_epoch writes a versioned checkpoint (params + optimizer state +
    # the full resume cursor) every ckpt_every optimizer steps;
    # Trainer.resume() restarts from the newest valid one mid-epoch,
    # bit-for-bit against an uninterrupted run.
    ckpt_dir: str | None = None
    ckpt_every: int = 0  # optimizer steps between checkpoints (0 = off)
    # Supervised producer pipeline (pipelined sources): transient build
    # failures (faults.RetryableError) retry in place up to plan_retries
    # times with exponential backoff; a delivery blocked longer than
    # stall_timeout_s raises faults.PipelineStallError naming the stuck
    # index instead of hanging the epoch. None = no watchdog.
    plan_retries: int = 0
    plan_retry_backoff_s: float = 0.05
    stall_timeout_s: float | None = None
    # Non-finite guard: detect NaN/Inf loss or gradients on device (one
    # fused isfinite reduction inside the existing jitted step — no extra
    # host sync) and skip that batch's optimizer update, counting
    # fault/nonfinite_skips. Determinism note: a skipped batch still
    # advances every RNG stream and the loss/acc it *reports* are the
    # non-finite values, so two runs with identical data remain bit-exact;
    # the guard changes the trajectory only on batches that would have
    # poisoned the params anyway.
    skip_nonfinite: bool = False
    seed: int = 0


log = logging.getLogger("repro.trainer")

#: wire bytes per element for each supported wire dtype (DESIGN.md §3a)
_WIRE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def modeled_wire_bytes(plan, spec: GNNSpec, wire_dtype: str) -> int:
    """Bytes the per-layer shuffles put on the wire for one plan (modeled).

    Counts only *true* cross-split rows (``LayerPlan.shuffle_rows`` — padding
    slots are free on real all-to-allv hardware and constant overhead here).
    Per row, the payload width depends on the schedule: the blocking path
    ships raw activations (``d_in``); the overlapped GAT path ships the
    transformed rows plus the eagerly exchanged a_src scores
    (``d_out + H`` — see ``_gnn_layer_overlap``). This is the §7 channel
    model: bytes are counted here, converted to seconds with testbed
    bandwidths by the benchmarks.
    """
    size = _WIRE_BYTES[wire_dtype]
    dims = spec.layer_dims()
    L = spec.num_layers
    total = 0
    for li, lp in enumerate(plan.layers):
        d_in, d_out = dims[L - 1 - li]
        if spec.model == "gat" and spec.overlap:
            per_row = d_out + spec.num_heads
        else:
            per_row = d_in
        total += lp.shuffle_rows() * per_row * size
    return total


@dataclass
class IterStats:
    loss: float
    accuracy: float
    t_sample: float
    t_split: float
    t_load: float
    t_compute: float
    loaded_rows: int
    computed_edges: int
    shuffle_rows: int
    padded_edge_slots: int = 0
    busiest_edges: int = 0
    load_breakdown: LoadBreakdown | None = None
    load_imbalance: float = 1.0
    cross_edge_fraction: float = 0.0
    wire_bytes: int = 0  # modeled shuffle bytes on the wire (see above)


@dataclass
class EpochStats:
    iters: list[IterStats] = field(default_factory=list)
    pipeline: dict = field(default_factory=dict)  # queue/signature stats
    t_wall: float = 0.0  # consumer wall time for the whole epoch
    t_first_iter: float = 0.0  # includes pipeline fill (first-batch wait)
    # jit cache misses this epoch (trace_recompiles=True): {"steps", "misses",
    # "by_fn", "miss_steps"} from runtime.recompile.RecompileTracer.since()
    recompiles: dict = field(default_factory=dict)

    def steady_step_seconds(self) -> float:
        """Per-step wall time excluding the pipeline-fill first iteration."""
        n = len(self.iters)
        if n <= 1:
            return self.t_wall / max(n, 1)
        return (self.t_wall - self.t_first_iter) / (n - 1)

    def totals(self) -> dict:
        agg = {
            "loss": float(np.mean([i.loss for i in self.iters])),
            "accuracy": float(np.mean([i.accuracy for i in self.iters])),
        }
        for k in (
            "t_sample",
            "t_split",
            "t_load",
            "t_compute",
            "loaded_rows",
            "computed_edges",
            "shuffle_rows",
            "padded_edge_slots",
            "busiest_edges",
            "wire_bytes",
        ):
            agg[k] = float(np.sum([getattr(i, k) for i in self.iters]))
        agg["load_imbalance"] = float(
            np.mean([i.load_imbalance for i in self.iters])
        )
        agg["cross_edge_fraction"] = float(
            np.mean([i.cross_edge_fraction for i in self.iters])
        )
        if self.iters and self.iters[0].load_breakdown is not None:
            agg["load_local_hit"] = int(
                np.sum([i.load_breakdown.local_hit for i in self.iters])
            )
            agg["load_remote_hit"] = int(
                np.sum([i.load_breakdown.remote_hit for i in self.iters])
            )
            agg["load_host_miss"] = int(
                np.sum([i.load_breakdown.host_miss for i in self.iters])
            )
        return agg


class Trainer:
    """End-to-end mini-batch GNN training with the chosen parallelism."""

    def __init__(
        self,
        dataset: GraphDataset,
        spec: GNNSpec,
        cfg: TrainConfig,
        injector=None,  # repro.faults.FaultInjector | None (chaos hooks)
    ):
        from dataclasses import replace

        from repro.core.shuffle import WIRE_DTYPES

        if cfg.wire_dtype not in WIRE_DTYPES:
            raise ValueError(
                f"unknown wire_dtype {cfg.wire_dtype!r} (one of {WIRE_DTYPES})"
            )
        if cfg.shuffle_chunks < 1:
            raise ValueError("shuffle_chunks must be >= 1")
        if cfg.num_replicas < 0:
            raise ValueError("num_replicas must be >= 0 (0 = 1D split path)")
        if cfg.num_replicas >= 1 and cfg.mode != "split":
            raise ValueError(
                "the (R, P) mesh composes with mode='split' only — dp and "
                "pushpull are already replica-style baselines"
            )
        self.ds = dataset
        # one obs sink per trainer when tracing; the shared disabled
        # singleton otherwise (single code path — see repro.obs)
        self.obs = Obs(enabled=True) if cfg.obs_trace else NULL_OBS
        # the config's execution-schedule knobs are authoritative: the spec
        # the caller hands in describes the model, the TrainConfig describes
        # how this trainer runs it
        self.spec = spec = replace(
            spec,
            overlap=cfg.shuffle_overlap,
            shuffle_chunks=cfg.shuffle_chunks,
            wire_dtype=cfg.wire_dtype,
        )
        self.cfg = cfg
        self.sampler = NeighborSampler(
            dataset.graph,
            dataset.train_ids,
            list(cfg.fanouts),
            cfg.batch_size,
            seed=cfg.seed,
        )

        # ---- offline stage: presample + partition (split mode) -------------
        self.weights = None
        self.partition = None
        t0 = time.perf_counter()
        if cfg.mode == "split" or cfg.cache_mode != "none":
            self.weights = presample(
                dataset.graph,
                dataset.train_ids,
                list(cfg.fanouts),
                cfg.batch_size,
                num_epochs=cfg.presample_epochs,
                seed=cfg.seed + 1,
                workers=cfg.presample_workers,
            )
        self.t_presample = time.perf_counter() - t0
        t0 = time.perf_counter()
        if cfg.mode == "split":
            self.partition = partition_graph(
                dataset.graph,
                cfg.num_devices,
                method=cfg.partition_method,
                weights=self.weights,
                train_ids=dataset.train_ids,
                seed=cfg.seed,
                replication_budget=cfg.replication_budget,
            )
        self.t_partition = time.perf_counter() - t0

        # hot-vertex replication: the selected rows become a device-resident
        # (R, F) block appended past the recv region of the mixed buffer
        self.replication = self.partition.replication if self.partition else None
        self.rep_block = None
        if self.replication is not None:
            self.rep_block = jnp.asarray(
                dataset.features[self.replication.vertices].astype(
                    np.float32, copy=False
                )
            )
        self.telemetry = None
        if cfg.record_telemetry and cfg.mode == "split":
            from repro.core.partition import EdgeTelemetry

            self.telemetry = EdgeTelemetry(
                dataset.graph.num_nodes, dataset.graph.num_edges
            )

        self.cache = None
        self.cache_block = None  # (P, C, F) device-resident rows when serving
        if cfg.cache_mode != "none":
            self.cache = FeatureCache(
                dataset.graph.num_nodes,
                cfg.num_devices,
                cfg.cache_capacity_per_device,
                ranking=self.weights.vertex_weight,
                mode=cfg.cache_mode,
                partition_assignment=(
                    self.partition.assignment if self.partition else None
                ),
            )
            if cfg.cache_serve and self.cache.serves:
                self.cache_block = jnp.asarray(
                    self.cache.build_resident(dataset.features)
                )

        key = jax.random.PRNGKey(cfg.seed)
        self.params = init_gnn_params(key, spec)
        opt_factory = getattr(opt_lib, cfg.optimizer)
        self.opt = opt_factory(cfg.lr)
        self.opt_state = self.opt.init(self.params)
        self._step_fn, self._cached_step_fn = self._build_step()
        self._mesh_step_fn = self._mesh_cached_step_fn = None
        if cfg.num_replicas >= 1:
            self._mesh_step_fn, self._mesh_cached_step_fn = (
                self._build_mesh_step()
            )
        self._pad_hwm: dict = {}  # high-water-mark padding (stable jit sigs)
        self._epoch = 0  # epochs consumed via train_epoch (keyed RNG input)
        self._start_iter = 0  # resume cursor: first batch of the next epoch
        self.global_step = 0  # optimizer steps taken (checkpoint naming)
        self.nonfinite_skips = 0  # batches whose update the guard skipped
        self.injector = injector
        self.sig_cache = SignatureCache()
        self.device_sampler = None
        if cfg.plan_source in ("device", "device_pipelined"):
            from repro.sampler import DeviceSampler

            if cfg.mode != "split":
                raise ValueError("plan_source 'device' requires mode='split'")
            self.device_sampler = DeviceSampler(
                dataset.graph,
                self.partition.assignment,
                cfg.num_devices,
                list(cfg.fanouts),
                cfg.seed,
                host_sampler=self.sampler,
                backend=cfg.sampler_backend,
            )
            self.device_sampler.obs = self.obs
        self.recompiles = None
        if cfg.trace_recompiles:
            from repro.runtime.recompile import RecompileTracer

            self.recompiles = RecompileTracer()
            self.recompiles.register("step", self._step_fn)
            self.recompiles.register("cached_step", self._cached_step_fn)
            if self._mesh_step_fn is not None:
                self.recompiles.register("mesh_step", self._mesh_step_fn)
                self.recompiles.register(
                    "mesh_cached_step", self._mesh_cached_step_fn
                )
            if self.device_sampler is not None:
                from repro.sampler.engine import _sample_device

                self.recompiles.register("sample_device", _sample_device)
        # host feature blocks are written into reused blocks of this pool;
        # each goes back only once the step that read it has synced: on the
        # CPU backend ``jnp.asarray`` may alias a block rather than copy it
        # (DESIGN.md §6)
        self.feature_pool = FeatureBlockPool()
        self.producer = PlanProducer(
            self.sampler,
            dataset.features,
            dataset.labels,
            mode=cfg.mode,
            num_devices=cfg.num_devices,
            pad_multiple=cfg.pad_multiple,
            assignment=self.partition.assignment if self.partition else None,
            cache=self.cache,
            serve_cache=self.cache_block is not None,
            device_sampler=self.device_sampler,
            with_halves=cfg.shuffle_overlap,
            replication=self.replication,
            telemetry=self.telemetry,
            num_replicas=cfg.num_replicas,
            obs=self.obs,
            injector=injector,
            pool=self.feature_pool,
        )

    # ------------------------------------------------------------------ #
    def _build_step(self):
        spec, opt = self.spec, self.opt
        skip_nonfinite = self.cfg.skip_nonfinite  # static: fixed return arity

        def make_step(forward_fn):
            """One jitted update step; ``inputs`` is the feature pytree —
            a (P, N_L, F) block, or (cache_block, miss_feats) when served.
            One factory guarantees cached and uncached steps share the exact
            loss/update math (the serving path must never drift).

            With ``skip_nonfinite`` the step returns a fifth output — a
            device bool that is False when the loss or any gradient leaf is
            non-finite — and the update is a ``where``-select against the
            old params/opt state, so a poisoned batch costs one fused
            reduction instead of a host round-trip (docs/ROBUSTNESS.md)."""

            def loss_fn(params, inputs, plan_arrays, labels):
                logits = forward_fn(params, inputs, plan_arrays)
                mask = plan_arrays["target_mask"]
                with jax.named_scope("loss"):
                    loss = masked_softmax_xent(logits, labels, mask)
                    acc = masked_accuracy(logits, labels, mask)
                return loss, acc

            if not skip_nonfinite:

                @jax.jit
                def step(params, opt_state, inputs, plan_arrays, labels):
                    (loss, acc), grads = jax.value_and_grad(
                        loss_fn, has_aux=True
                    )(params, inputs, plan_arrays, labels)
                    with jax.named_scope("optimizer"):
                        params, opt_state = opt.update(grads, opt_state, params)
                    return params, opt_state, loss, acc

                return step

            @jax.jit
            def guarded_step(params, opt_state, inputs, plan_arrays, labels):
                (loss, acc), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params, inputs, plan_arrays, labels)
                finite = jnp.isfinite(loss)
                for leaf in jax.tree_util.tree_leaves(grads):
                    finite = finite & jnp.all(jnp.isfinite(leaf))
                with jax.named_scope("optimizer"):
                    new_params, new_opt_state = opt.update(
                        grads, opt_state, params
                    )
                params = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(finite, new, old),
                    new_params, params,
                )
                opt_state = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(finite, new, old),
                    new_opt_state, opt_state,
                )
                return params, opt_state, loss, acc, finite

            return guarded_step

        # the replicated block rides in the plan pytree under "rep" (absent
        # when replication is off — dict structure keys the jit trace), so
        # Trainer.refine_partition can swap the block without stale closures
        step = make_step(
            lambda params, feats, pa: gnn_forward(
                spec, params, feats, pa, sim_shuffle, rep_block=pa.get("rep")
            )
        )
        cached_step = make_step(
            lambda params, inputs, pa: gnn_forward_cached(
                spec, params, inputs[0], inputs[1], pa, sim_shuffle,
                rep_block=pa.get("rep"),
            )
        )
        return step, cached_step

    def _build_mesh_step(self):
        """The 2D (replica, split) step: R split-local forward/backwards in
        one jitted call, gradients averaged across the replica axis.

        ``replicas`` is a tuple of R ``(inputs, plan_arrays, labels)``
        triples — one per replica group, each carrying its own leading-P
        plan pytree (R is static program structure via the tuple length, so
        the signature cache keys on the mesh shape). The replica loop is
        *unrolled in Python* rather than vmapped: each iteration traces the
        exact jaxpr of the 1D step's loss/grad, which makes the R = 1 mesh
        bit-identical to the 1D path (the trailing sum-of-one-term and
        divide-by-1.0 are IEEE-exact) — the anchor of the equivalence
        matrix in tests/test_mesh.py. The fixed left-to-right reduction
        over replicas is the sim statement of the spmd psum's ring order
        (``core.shuffle.replica_grad_mean``). The loss/accuracy reported
        are the means of the per-replica masked means.
        """
        spec, opt = self.spec, self.opt
        skip_nonfinite = self.cfg.skip_nonfinite  # static: fixed return arity

        def make_step(forward_fn):
            def loss_fn(params, inputs, plan_arrays, labels):
                logits = forward_fn(params, inputs, plan_arrays)
                mask = plan_arrays["target_mask"]
                with jax.named_scope("loss"):
                    loss = masked_softmax_xent(logits, labels, mask)
                    acc = masked_accuracy(logits, labels, mask)
                return loss, acc

            @jax.jit
            def mesh_step(params, opt_state, replicas):
                grads = loss_sum = acc_sum = None
                for inputs, plan_arrays, labels in replicas:
                    (loss, acc), g = jax.value_and_grad(
                        loss_fn, has_aux=True
                    )(params, inputs, plan_arrays, labels)
                    grads = (
                        g
                        if grads is None
                        else jax.tree_util.tree_map(jnp.add, grads, g)
                    )
                    loss_sum = loss if loss_sum is None else loss_sum + loss
                    acc_sum = acc if acc_sum is None else acc_sum + acc
                num = len(replicas)
                grads = jax.tree_util.tree_map(lambda t: t / num, grads)
                if not skip_nonfinite:
                    with jax.named_scope("optimizer"):
                        params, opt_state = opt.update(
                            grads, opt_state, params
                        )
                    return params, opt_state, loss_sum / num, acc_sum / num
                # guard the *averaged* gradient: any replica's NaN/Inf
                # poisons the mean, so one check covers all R branches
                finite = jnp.isfinite(loss_sum)
                for leaf in jax.tree_util.tree_leaves(grads):
                    finite = finite & jnp.all(jnp.isfinite(leaf))
                with jax.named_scope("optimizer"):
                    new_params, new_opt_state = opt.update(
                        grads, opt_state, params
                    )
                params = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(finite, new, old),
                    new_params, params,
                )
                opt_state = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(finite, new, old),
                    new_opt_state, opt_state,
                )
                return (
                    params, opt_state, loss_sum / num, acc_sum / num, finite
                )

            return mesh_step

        mesh_step = make_step(
            lambda params, feats, pa: gnn_forward(
                spec, params, feats, pa, sim_shuffle, rep_block=pa.get("rep")
            )
        )
        mesh_cached_step = make_step(
            lambda params, inputs, pa: gnn_forward_cached(
                spec, params, inputs[0], inputs[1], pa, sim_shuffle,
                rep_block=pa.get("rep"),
            )
        )
        return mesh_step, mesh_cached_step

    def _num_replicated(self) -> int:
        return self.replication.num_replicated if self.replication else 0

    def _attach_rep(self, plan_arrays: dict) -> dict:
        if self.rep_block is not None:
            plan_arrays["rep"] = self.rep_block
        return plan_arrays

    # ------------------------------------------------------------------ #
    def _dispatch_step(self, fn, *args):
        """Dispatch one jitted step and unpack by the configured arity.

        Returns the still-async ``(loss, acc, finite)`` device values;
        ``finite`` is None when the non-finite guard is off (the step
        returns 4 outputs) and a device bool when it is on (5 outputs).
        """
        out = fn(self.params, self.opt_state, *args)
        if self.cfg.skip_nonfinite:
            self.params, self.opt_state, loss, acc, finite = out
            return loss, acc, finite
        self.params, self.opt_state, loss, acc = out
        return loss, acc, None

    def _sync_step(self, loss, acc, finite):
        """The single designed device sync point: one transfer fetches both
        scalars — and the finite flag rides the *same* transfer when the
        guard is on, so detecting a skipped batch costs zero extra syncs."""
        if finite is None:
            loss, acc = jax.device_get((loss, acc))
            return float(loss), float(acc), None
        loss, acc, finite = jax.device_get((loss, acc, finite))
        if not bool(finite):
            self.nonfinite_skips += 1
            self.obs.count("fault/nonfinite_skips", 1)
            self.obs.instant(
                "fault/nonfinite_skip",
                {"step": self.global_step, "loss": repr(float(loss))},
            )
            log.warning(
                "non-finite loss/gradients at step %d — optimizer update "
                "skipped (loss=%r)", self.global_step, float(loss),
            )
        return float(loss), float(acc), bool(finite)

    # ------------------------------------------------------------------ #
    def _plan_for(self, targets: np.ndarray):
        cfg = self.cfg
        with self.obs.span("plan/sample") as sp_sample:
            if cfg.mode in ("dp", "pushpull"):
                samples = self.sampler.sample_micro(targets, cfg.num_devices)
            else:
                sample = self.sampler.sample(targets)
        with self.obs.span("plan/split") as sp_split:
            if cfg.mode in ("dp", "pushpull"):
                plan = build_dp_plan(
                    samples, pad_multiple=cfg.pad_multiple,
                    with_halves=cfg.shuffle_overlap,
                )
            else:
                plan = build_split_plan(
                    sample,
                    self.partition.assignment,
                    cfg.num_devices,
                    pad_multiple=cfg.pad_multiple,
                    with_halves=cfg.shuffle_overlap,
                    replication=self.replication,
                )
            before = dict(self._pad_hwm)
            plan = repad_plan(plan, self._pad_hwm)
        note_hwm_growth(self.obs, before, self._pad_hwm, "train_iter")
        return plan, sp_sample.duration, sp_split.duration

    def _mesh_plan_for(self, targets: np.ndarray):
        """Inline-path mesh fan-out: R streamed samples -> R repadded plans.

        Mirrors ``_plan_for`` on the streamed (call-order) RNG: replica
        chunks consume the shared generator sequentially, exactly like
        ``sample_micro`` does for dp. Two repad passes against the shared
        high-water marks leave the R plans rectangular (same discipline as
        the delivery-side ``_finalize_mesh``); with R == 1 the second pass
        is a no-op and this is ``_plan_for`` verbatim.
        """
        cfg = self.cfg
        R = cfg.num_replicas
        with self.obs.span("plan/sample") as sp_sample:
            chunks = [targets] if R == 1 else np.array_split(targets, R)
            samples = [self.sampler.sample(c) for c in chunks]
        with self.obs.span("plan/split") as sp_split:
            plans = [
                build_split_plan(
                    s,
                    self.partition.assignment,
                    cfg.num_devices,
                    pad_multiple=cfg.pad_multiple,
                    with_halves=cfg.shuffle_overlap,
                    replication=self.replication,
                )
                for s in samples
            ]
            before = dict(self._pad_hwm)
            for _ in range(2):
                for plan in plans:
                    repad_plan(plan, self._pad_hwm)
        note_hwm_growth(self.obs, before, self._pad_hwm, "train_iter")
        return plans, sp_sample.duration, sp_split.duration

    def _train_iter_mesh(self, targets: np.ndarray) -> IterStats:
        plans, t_sample, t_split = self._mesh_plan_for(targets)

        with self.obs.span("plan/load") as sp_load:
            staged = []  # [plan, cache_plan, feats, labels, breakdown]
            for plan in plans:
                cache_plan, feats, breakdown, _ = stage_host_features(
                    plan, self.ds.features, self.cache,
                    serve_cache=self.cache_block is not None,
                    pad_multiple=self.cfg.pad_multiple,
                    pool=self.feature_pool,
                )
                labels = load_labels(plan, self.ds.labels)
                staged.append([plan, cache_plan, feats, labels, breakdown])
            # cache widths follow the shared CM/CS marks, settled over all R
            # parts before any feature block is padded (two-pass, like plans)
            for _ in range(2):
                for plan, cache_plan, *_ in staged:
                    if cache_plan is not None:
                        finalize_cache_plan(
                            cache_plan, self._pad_hwm,
                            plan.front_ids[-1].shape[1],
                        )
            for entry in staged:
                if entry[1] is not None:
                    entry[2] = pad_block(
                        entry[2], self._pad_hwm["CM"], self.feature_pool
                    )

        with self.obs.span(
            "step", {"wait_s": 0.0}, step_num=self.global_step
        ) as step_sp:
            with self.obs.span("step/stage") as sp_stage:
                cached = staged[0][1] is not None
                replicas = []
                for plan, cache_plan, feats, labels, _ in staged:
                    plan_arrays = self._attach_rep(
                        plan_to_device(
                            plan, cache_plan,
                            with_halves=self.cfg.shuffle_overlap,
                            num_replicated=self._num_replicated(),
                        )
                    )
                    inputs = (
                        (self.cache_block, jnp.asarray(feats))
                        if cached
                        else jnp.asarray(feats)
                    )
                    replicas.append((inputs, plan_arrays, jnp.asarray(labels)))
                fn = self._mesh_cached_step_fn if cached else self._mesh_step_fn
                loss, acc, finite = self._dispatch_step(fn, tuple(replicas))
            if self.recompiles is not None:
                self.recompiles.step("train_iter")
            with self.obs.span("step/device") as sp_dev:
                loss, acc, finite = self._sync_step(loss, acc, finite)
            for entry in staged:
                self.feature_pool.release(entry[2])
            step_sp.attrs.update(
                stage_s=sp_stage.duration, device_s=sp_dev.duration
            )
        self.global_step += 1
        return self._mesh_iter_stats(
            plans,
            [entry[4] for entry in staged],
            loss,
            acc,
            t_sample,
            t_split,
            sp_load.duration,
            sp_stage.duration + sp_dev.duration,
        )

    def train_iter(self, targets: np.ndarray) -> IterStats:
        if self.cfg.num_replicas >= 1:
            return self._train_iter_mesh(targets)
        plan, t_sample, t_split = self._plan_for(targets)

        with self.obs.span("plan/load") as sp_load:
            cache_plan, feats, breakdown, _ = stage_host_features(
                plan, self.ds.features, self.cache,
                serve_cache=self.cache_block is not None,
                pad_multiple=self.cfg.pad_multiple,
                pool=self.feature_pool,
            )
            if cache_plan is not None:
                # widths follow the same high-water marks as the plan itself
                # (stable jit signatures); _plan_for already repadded the plan
                finalize_cache_plan(
                    cache_plan, self._pad_hwm, plan.front_ids[-1].shape[1]
                )
                feats = pad_block(
                    feats, self._pad_hwm["CM"], self.feature_pool
                )
            labels = load_labels(plan, self.ds.labels)

        with self.obs.span(
            "step", {"wait_s": 0.0}, step_num=self.global_step
        ) as step_sp:
            with self.obs.span("step/stage") as sp_stage:
                plan_arrays = self._attach_rep(
                    plan_to_device(
                        plan, cache_plan, with_halves=self.cfg.shuffle_overlap,
                        num_replicated=self._num_replicated(),
                    )
                )
                if cache_plan is not None:
                    loss, acc, finite = self._dispatch_step(
                        self._cached_step_fn,
                        (self.cache_block, jnp.asarray(feats)),
                        plan_arrays, jnp.asarray(labels),
                    )
                else:
                    loss, acc, finite = self._dispatch_step(
                        self._step_fn, jnp.asarray(feats),
                        plan_arrays, jnp.asarray(labels),
                    )
            if self.recompiles is not None:
                self.recompiles.step("train_iter")
            # one transfer for both scalars: float(loss); float(acc) would
            # pay two round-trips to the device
            with self.obs.span("step/device") as sp_dev:
                loss, acc, finite = self._sync_step(loss, acc, finite)
            self.feature_pool.release(feats)
            step_sp.attrs.update(
                stage_s=sp_stage.duration, device_s=sp_dev.duration
            )
        self.global_step += 1

        st = IterStats(
            loss=loss,
            accuracy=acc,
            t_sample=t_sample,
            t_split=t_split,
            t_load=sp_load.duration,
            t_compute=sp_stage.duration + sp_dev.duration,
            loaded_rows=plan.loaded_feature_rows(),
            computed_edges=plan.computed_edges(),
            shuffle_rows=plan.shuffle_rows(),
            padded_edge_slots=plan.padded_edge_slots(),
            busiest_edges=plan.busiest_edges(),
            load_breakdown=breakdown,
            load_imbalance=plan.load_imbalance(),
            cross_edge_fraction=plan.cross_edge_fraction(),
            wire_bytes=modeled_wire_bytes(plan, self.spec, self.cfg.wire_dtype),
        )
        self._emit_iter_metrics(st)
        return st

    # ------------------------------------------------------------------ #
    def plan_source_for(
        self, epoch: int, max_iters: int | None = None, start: int = 0
    ):
        """A ``PlanSource`` over the given epoch's batches (keyed RNG).

        ``start`` resumes mid-epoch: batches before it are skipped, but
        every delivered batch keeps its original global index for RNG
        keying, so the tail of a resumed epoch is bit-identical to the
        tail of an uninterrupted one.
        """
        batches = self.sampler.epoch_targets(epoch)
        if max_iters is not None:
            batches = batches[:max_iters]
        batches = batches[start:]
        retry = None
        if self.cfg.plan_retries > 0:
            retry = RetryPolicy(
                retries=self.cfg.plan_retries,
                backoff_s=self.cfg.plan_retry_backoff_s,
            )
        return make_plan_source(
            self.cfg.plan_source,
            self.producer,
            epoch,
            batches,
            self._pad_hwm,
            self.sig_cache,
            depth=self.cfg.pipeline_depth,
            workers=self.cfg.plan_workers,
            sig_extra=(
                self.cfg.wire_dtype,
                self.cfg.shuffle_chunks,
                self.cfg.shuffle_overlap,
            ),
            obs=self.obs,
            start=start,
            retry=retry,
            stall_timeout_s=self.cfg.stall_timeout_s,
        )

    def _step_mesh_batch(self, batch: MeshPlanBatch):
        """Stage all R parts of a mesh batch and dispatch the mesh step.

        Each part stages exactly like a 1D batch (same ``stage_batch``,
        same replicated-block attachment — the resident block is one
        object shared by every replica's plan pytree, no copies); the
        jitted mesh step consumes the R triples in replica order.
        """
        cached = batch.parts[0].cache_plan is not None
        replicas = []
        with self.obs.span("step/put") as sp_put:
            staged = [
                stage_batch(
                    part.plan, part.feats, part.labels, part.cache_plan,
                    with_halves=self.cfg.shuffle_overlap,
                    num_replicated=self._num_replicated(),
                )
                for part in batch.parts
            ]
            if self.obs.enabled:
                sp_put.set(**transfer_counts(staged))
            for feats_d, plan_arrays, labels_d in staged:
                plan_arrays = self._attach_rep(plan_arrays)
                inputs = (self.cache_block, feats_d) if cached else feats_d
                replicas.append((inputs, plan_arrays, labels_d))
        fn = self._mesh_cached_step_fn if cached else self._mesh_step_fn
        with self.obs.span("step/dispatch"):
            return self._dispatch_step(fn, tuple(replicas))

    def _step_batch(self, batch: PlanBatch):
        """Stage a finalized batch to device and dispatch the jitted step.
        Returns the (still-async) ``(loss, acc, finite)`` device values."""
        if isinstance(batch, MeshPlanBatch):
            return self._step_mesh_batch(batch)
        with self.obs.span("step/put") as sp_put:
            staged = stage_batch(
                batch.plan, batch.feats, batch.labels, batch.cache_plan,
                with_halves=self.cfg.shuffle_overlap,
                num_replicated=self._num_replicated(),
            )
            if self.obs.enabled:
                sp_put.set(**transfer_counts(staged))
            feats_d, plan_arrays, labels_d = staged
            plan_arrays = self._attach_rep(plan_arrays)
        with self.obs.span("step/dispatch"):
            if batch.cache_plan is not None:
                return self._dispatch_step(
                    self._cached_step_fn, (self.cache_block, feats_d),
                    plan_arrays, labels_d,
                )
            return self._dispatch_step(
                self._step_fn, feats_d, plan_arrays, labels_d
            )

    def _mesh_iter_stats(
        self, plans, breakdowns, loss, acc, t_sample, t_split, t_load,
        t_compute,
    ) -> IterStats:
        """Aggregate R per-replica plans into one global-batch IterStats.

        Work counters (loaded rows, edges, shuffle rows, wire bytes, padded
        slots) sum — they are real total work for the global batch; the
        balance ratios average; ``busiest_edges`` takes the max — all R*P
        devices run concurrently, so the busiest device anywhere is the
        step's compute critical path.
        """
        breakdown = None
        if breakdowns and all(b is not None for b in breakdowns):
            breakdown = LoadBreakdown(
                local_hit=sum(b.local_hit for b in breakdowns),
                remote_hit=sum(b.remote_hit for b in breakdowns),
                host_miss=sum(b.host_miss for b in breakdowns),
            )
        st = IterStats(
            loss=float(loss),
            accuracy=float(acc),
            t_sample=t_sample,
            t_split=t_split,
            t_load=t_load,
            t_compute=t_compute,
            loaded_rows=sum(p.loaded_feature_rows() for p in plans),
            computed_edges=sum(p.computed_edges() for p in plans),
            shuffle_rows=sum(p.shuffle_rows() for p in plans),
            padded_edge_slots=sum(p.padded_edge_slots() for p in plans),
            busiest_edges=max(p.busiest_edges() for p in plans),
            load_breakdown=breakdown,
            load_imbalance=float(
                np.mean([p.load_imbalance() for p in plans])
            ),
            cross_edge_fraction=float(
                np.mean([p.cross_edge_fraction() for p in plans])
            ),
            wire_bytes=sum(
                modeled_wire_bytes(p, self.spec, self.cfg.wire_dtype)
                for p in plans
            ),
        )
        self._emit_iter_metrics(st)
        return st

    def _emit_iter_metrics(self, st: IterStats) -> None:
        """Fold one step's IterStats into the metrics registry (no-op when
        obs is disabled — the counters mirror what EpochStats.totals() sums,
        so a written trace is self-contained without the stats object)."""
        obs = self.obs
        if not obs.enabled:
            return
        obs.count("wire/bytes", st.wire_bytes)
        obs.count("plan/loaded_rows", st.loaded_rows)
        obs.count("plan/shuffle_rows", st.shuffle_rows)
        if st.load_breakdown is not None:
            obs.count("cache/local_hit", st.load_breakdown.local_hit)
            obs.count("cache/remote_hit", st.load_breakdown.remote_hit)
            obs.count("cache/host_miss", st.load_breakdown.host_miss)

    def _iter_stats(
        self, batch: PlanBatch, loss: float, acc: float, t_compute: float
    ) -> IterStats:
        """IterStats for one delivered batch; ``loss``/``acc`` are already
        host floats (the epoch loop owns the device_get sync point)."""
        if isinstance(batch, MeshPlanBatch):
            return self._mesh_iter_stats(
                [p.plan for p in batch.parts],
                [p.breakdown for p in batch.parts],
                loss,
                acc,
                batch.t_sample,
                batch.t_split,
                batch.t_load,
                t_compute,
            )
        plan = batch.plan
        st = IterStats(
            loss=loss,
            accuracy=acc,
            t_sample=batch.t_sample,
            t_split=batch.t_split,
            t_load=batch.t_load,
            t_compute=t_compute,
            loaded_rows=plan.loaded_feature_rows(),
            computed_edges=plan.computed_edges(),
            shuffle_rows=plan.shuffle_rows(),
            padded_edge_slots=plan.padded_edge_slots(),
            busiest_edges=plan.busiest_edges(),
            load_breakdown=batch.breakdown,
            load_imbalance=plan.load_imbalance(),
            cross_edge_fraction=plan.cross_edge_fraction(),
            wire_bytes=modeled_wire_bytes(plan, self.spec, self.cfg.wire_dtype),
        )
        self._emit_iter_metrics(st)
        return st

    def train_epoch(self, max_iters: int | None = None) -> EpochStats:
        """One epoch through the configured plan source.

        With the ``pipelined`` source the host producers run ahead behind a
        bounded queue, so each delivered ``PlanBatch`` arrives fully staged
        (plan + feature/label blocks — the queue slots are the double
        buffer) and the consumer only pays transfer + step. Numerics are
        identical to ``serial`` because delivery order, RNG streams, and
        padded shapes all match (DESIGN.md §6). The consumer deliberately
        blocks on each step's result before dispatching the next: on the
        CPU backend, queueing a second step while one is in flight was
        measured consistently *slower* (extra staging traffic competes with
        the running computation), while producer prefetch alone gives the
        overlap win.
        """
        stats = EpochStats()
        # mid-epoch resume: the cursor's batch offset applies to exactly one
        # epoch (the one the checkpoint was taken in), then clears
        start, self._start_iter = self._start_iter, 0
        source = self.plan_source_for(self._epoch, max_iters, start=start)
        n_batches = start + len(source.batches)  # this epoch's global count
        mark = self.recompiles.mark() if self.recompiles is not None else None
        t_epoch = time.perf_counter()
        try:
            it = iter(source)
            while True:
                # time blocked on the source: the producer-bound component
                # of the step (serial sources do the whole build here)
                with self.obs.span("step/wait") as sp_wait:
                    batch = next(it, None)
                if batch is None:
                    break
                with self.obs.span(
                    "step", {"epoch": batch.epoch, "batch": batch.index},
                    step_num=self.global_step,
                ) as step_sp:
                    # close the flow arrow from this plan's producer span
                    self.obs.flow_end(("plan", batch.epoch, batch.index))
                    with self.obs.span("step/stage") as sp_stage:
                        loss, acc, finite = self._step_batch(batch)
                    # one transfer fetches both scalars (plus the finite
                    # flag under skip_nonfinite) and blocks until the step's
                    # results are ready — the epoch loop's single designed
                    # sync point
                    with self.obs.span("step/device") as sp_dev:
                        loss, acc, finite = self._sync_step(loss, acc, finite)
                    # the step has read its inputs: hand its feature
                    # blocks back to the pool
                    parts = (batch.parts if isinstance(batch, MeshPlanBatch)
                             else [batch])
                    for part in parts:
                        self.feature_pool.release(part.feats)
                    step_sp.attrs.update(
                        wait_s=sp_wait.duration,
                        stage_s=sp_stage.duration,
                        device_s=sp_dev.duration,
                    )
                stats.iters.append(
                    self._iter_stats(
                        batch, loss, acc,
                        sp_stage.duration + sp_dev.duration,
                    )
                )
                self.global_step += 1
                if (
                    self.cfg.ckpt_dir
                    and self.cfg.ckpt_every > 0
                    and self.global_step % self.cfg.ckpt_every == 0
                ):
                    next_batch = batch.index + 1
                    epoch, next_batch = (
                        (self._epoch + 1, 0)
                        if next_batch >= n_batches
                        else (self._epoch, next_batch)
                    )
                    self.save_checkpoint(epoch=epoch, next_batch=next_batch)
                if self.recompiles is not None:
                    self.recompiles.step(f"epoch{self._epoch}")
                if stats.t_first_iter == 0.0:
                    stats.t_first_iter = time.perf_counter() - t_epoch
        finally:
            source.close()
        if mark is not None:
            stats.recompiles = self.recompiles.since(mark)
            self.obs.count(
                "recompile/misses", int(stats.recompiles.get("misses", 0))
            )
        stats.pipeline = source.stats()
        stats.t_wall = time.perf_counter() - t_epoch
        if self.obs.enabled:
            self.obs.absorb(stats.pipeline, prefix="source/")
            if self.cfg.obs_path:
                self.obs.write(self.cfg.obs_path)
        self._epoch += 1
        return stats

    # ------------------------------------------------------------------ #
    def save_checkpoint(
        self,
        root: str | None = None,
        epoch: int | None = None,
        next_batch: int = 0,
    ) -> str:
        """Write one crash-consistent checkpoint (params + optimizer state +
        the full resume cursor) under ``root``/``cfg.ckpt_dir``.

        The cursor pins everything a bit-exact mid-epoch resume needs:
        the (epoch, batch) coordinate of the *next* batch, the global step,
        the RNG seed, the padding high-water marks (jit signatures), the
        device-sampler capacity table (device mode), and the telemetry
        counters (as aux arrays). ``train_epoch`` calls this every
        ``ckpt_every`` steps; it is also safe to call manually between
        epochs.
        """
        root = root if root is not None else self.cfg.ckpt_dir
        if not root:
            raise ValueError("no checkpoint directory (cfg.ckpt_dir unset)")
        cursor = {
            "epoch": int(self._epoch if epoch is None else epoch),
            "batch": int(next_batch),
            "global_step": int(self.global_step),
            "seed": int(self.cfg.seed),
            "hwm": {k: int(v) for k, v in self._pad_hwm.items()},
            "nonfinite_skips": int(self.nonfinite_skips),
            "sampler": (
                self.device_sampler.export_state()
                if self.device_sampler is not None
                else None
            ),
        }
        aux = {}
        if self.telemetry is not None:
            c = self.telemetry.counters()
            aux = {
                "telemetry_k_v": c["k_v"],
                "telemetry_k_e": c["k_e"],
                "telemetry_num_batches": np.asarray(c["num_batches"]),
            }
        path = os.path.join(root, checkpoint_name(self.global_step))
        _save_checkpoint(
            path,
            self.params,
            self.global_step,
            opt_state=self.opt_state,
            cursor=cursor,
            aux_arrays=aux,
        )
        self.obs.count("fault/checkpoints_written", 1)
        return path

    def resume(self, root: str | None = None):
        """Restore the newest valid checkpoint under ``root``/``cfg.ckpt_dir``.

        Rebuilds the exact mid-run state the cursor pinned — params,
        optimizer state, epoch/batch position, HWM padding dict, sampler
        caps, telemetry counters — so the continued trajectory is
        bit-for-bit the uninterrupted one. Corrupt newest checkpoints are
        skipped with a warning (previous-good fallback). Returns the loaded
        ``Checkpoint``, or None when the directory holds no checkpoint at
        all (fresh start).
        """
        root = root if root is not None else self.cfg.ckpt_dir
        if not root:
            raise ValueError("no checkpoint directory (cfg.ckpt_dir unset)")
        ck = load_latest_checkpoint(root, self.params, self.opt_state)
        if ck is None:
            return None
        cur = ck.cursor
        if "seed" in cur and int(cur["seed"]) != self.cfg.seed:
            log.warning(
                "resuming with seed %d but checkpoint was written with seed "
                "%d — the continued trajectory will NOT match the original",
                self.cfg.seed, int(cur["seed"]),
            )
        self.params = ck.params
        self.opt_state = ck.opt_state
        self.global_step = int(cur.get("global_step", ck.step))
        self._epoch = int(cur.get("epoch", 0))
        self._start_iter = int(cur.get("batch", 0))
        self.nonfinite_skips = int(cur.get("nonfinite_skips", 0))
        self._pad_hwm.clear()
        self._pad_hwm.update(
            {k: int(v) for k, v in cur.get("hwm", {}).items()}
        )
        if self.device_sampler is not None and cur.get("sampler"):
            self.device_sampler.load_state(cur["sampler"])
        if self.telemetry is not None and "telemetry_k_v" in ck.aux:
            self.telemetry.load_counters(
                {
                    "k_v": ck.aux["telemetry_k_v"],
                    "k_e": ck.aux["telemetry_k_e"],
                    "num_batches": int(ck.aux["telemetry_num_batches"]),
                }
            )
        self.obs.count("fault/resumes", 1)
        log.info(
            "resumed from %s at step %d (epoch %d, batch %d)",
            ck.path, self.global_step, self._epoch, self._start_iter,
        )
        return ck

    # ------------------------------------------------------------------ #
    def refine_partition(self, replication_budget: float | None = None):
        """Telemetry-driven partition refinement (method="telemetry").

        Call between epochs with ``record_telemetry=True``: the empirical
        per-edge appearance counts from the recorded training batches replace
        the presample estimates as edge weights, ``_refine`` re-runs from the
        current assignment, and the replication set is re-selected under the
        (possibly overridden) budget. The producer, resident block, and
        device sampler are all re-pointed at the new partition; plan-shape
        high-water marks are kept — shapes only ever grow, so already
        compiled steps stay valid. Returns the new ``Partition``.
        """
        from repro.core.partition import refine_partition as _refine_partition

        if self.partition is None:
            raise ValueError("refine_partition needs mode='split'")
        if self.telemetry is None:
            raise ValueError(
                "refine_partition needs record_telemetry=True (no telemetry "
                "was collected)"
            )
        budget = (
            self.cfg.replication_budget
            if replication_budget is None
            else replication_budget
        )
        self.partition = _refine_partition(
            self.ds.graph,
            self.partition,
            self.telemetry.as_weights(),
            replication_budget=budget,
        )
        self.replication = self.partition.replication
        self.rep_block = None
        if self.replication is not None:
            self.rep_block = jnp.asarray(
                self.ds.features[self.replication.vertices].astype(
                    np.float32, copy=False
                )
            )
        self.producer.assignment = self.partition.assignment
        self.producer.replication = self.replication
        if self.device_sampler is not None:
            from repro.sampler import DeviceSampler

            self.device_sampler = DeviceSampler(
                self.ds.graph,
                self.partition.assignment,
                self.cfg.num_devices,
                list(self.cfg.fanouts),
                self.cfg.seed,
                host_sampler=self.sampler,
                backend=self.cfg.sampler_backend,
            )
            self.device_sampler.obs = self.obs
            self.producer.device_sampler = self.device_sampler
        return self.partition
