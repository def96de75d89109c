"""Host plan -> device pytree conversion and feature loading.

Two loading paths feed the jitted step:

  * full host gather (``load_features``) — every input row crosses the host
    link; the only option without a cache.
  * cache serving — only the *miss* rows are host-gathered
    (``load_miss_features``); local/remote hits are assembled on device from
    the resident cache block (``core.shuffle.sim_serve_features``). The
    ``CachePlan`` arrays ride along in the plan pytree under ``"cache"``.

Both gathers can write into a block from a ``FeatureBlockPool`` instead of
a fresh array: a (P, N_L, F) float32 block is hundreds of MB at real
widths, and a fresh one per batch pays for first-touching every page of it
and unmapping it again.
"""
from __future__ import annotations

import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.splitting import SplitPlan, pad_axis
from repro.graph.cache import CachePlan


def cache_plan_to_device(cp: CachePlan) -> dict:
    """CachePlan -> jit-able pytree (host ``miss_ids`` stays behind)."""
    return {
        "local_slot": jnp.asarray(cp.local_slot, jnp.int32),
        "local_mask": jnp.asarray(cp.local_mask),
        "send_slot": jnp.asarray(cp.send_slot, jnp.int32),
        "recv_pos": jnp.asarray(cp.recv_pos, jnp.int32),
        "recv_mask": jnp.asarray(cp.recv_mask),
        "miss_pos": jnp.asarray(cp.miss_pos, jnp.int32),
        "miss_mask": jnp.asarray(cp.miss_mask),
    }


def plan_to_device(
    plan: SplitPlan,
    cache_plan: CachePlan | None = None,
    with_halves: bool = False,
    num_replicated: int = 0,
) -> dict:
    """Convert a SplitPlan into a jit-able pytree (indices as int32).

    ``with_halves`` ships the local/remote edge halves the overlap schedule
    consumes (DESIGN.md §3a) — opt-in end to end, like the builders'
    ``with_halves``: the blocking path neither builds the halves nor pays
    their host->device index transfers (~4 E-sized arrays + 2 packs per
    layer). The trainer threads its ``shuffle_overlap`` knob through both
    points; overlap-enabled plans build the halves on the producer threads,
    off the consumer's critical path under the pipelined source.

    ``num_replicated`` is the trainer's resident hot-vertex block height R
    (0 when replication is off). Plans built with a replication set address
    sources past the recv region under the assumption that exactly R
    replicated rows get appended to the mixed buffer — a mismatch between
    the plan and the block the step will serve is a silent wrong-gather, so
    it is rejected here, at staging time.
    """
    rep = plan.layers[-1].num_replicated if plan.layers else 0
    if rep != num_replicated:
        raise ValueError(
            f"plan carries {rep} replicated source rows but the trainer "
            f"serves a block of {num_replicated} — the plan builder and the "
            "resident replication block must come from the same "
            "ReplicationSet"
        )
    layers = []
    for lp in plan.layers:
        d = {
            "edge_src": jnp.asarray(lp.edge_src, jnp.int32),
            "edge_dst": jnp.asarray(lp.edge_dst, jnp.int32),
            "edge_mask": jnp.asarray(lp.edge_mask),
            "send_idx": jnp.asarray(lp.send_idx, jnp.int32),
            "self_pos": jnp.asarray(lp.self_pos, jnp.int32),
            # dst-sorted layout for the fused aggregation kernels
            # (docs/KERNELS.md). ~2 extra E-sized index transfers per
            # layer; XLA drops them when agg_backend == "jnp".
            "pack_perm": jnp.asarray(lp.pack_perm, jnp.int32),
            "pack_dst": jnp.asarray(lp.pack_dst, jnp.int32),
            "seg_offsets": jnp.asarray(lp.seg_offsets, jnp.int32),
        }
        if with_halves:
            if not lp.has_halves:
                raise ValueError(
                    "plan was built without edge halves "
                    "(build_*_plan(with_halves=False)) but the overlap "
                    "schedule needs them — builder and trainer must agree "
                    "on the shuffle_overlap knob"
                )
            # local/remote edge halves for the overlap schedule (§3a)
            for k in (
                "ledge_src", "ledge_dst", "ledge_mask", "ledge_ids",
                "lpack_perm", "lpack_dst",
                "redge_src", "redge_dst", "redge_mask", "redge_ids",
                "rpack_perm", "rpack_dst",
            ):
                a = getattr(lp, k)
                d[k] = jnp.asarray(a) if a.dtype == bool else jnp.asarray(
                    a, jnp.int32
                )
        layers.append(d)
    out = {
        "layers": layers,
        "target_mask": jnp.asarray(plan.node_mask[0]),
        "input_mask": jnp.asarray(plan.node_mask[-1]),
    }
    if cache_plan is not None:
        out["cache"] = cache_plan_to_device(cache_plan)
    return out


def stage_batch(
    plan: SplitPlan,
    feats: np.ndarray,
    labels: np.ndarray,
    cache_plan: CachePlan | None = None,
    with_halves: bool = False,
    num_replicated: int = 0,
) -> tuple:
    """Host -> device transfer of one staged batch (plan + features + labels).

    With a cache plan, ``feats`` is the small (P, M, F) miss block instead of
    the full (P, N_L, F) gather. One call site for the transfer keeps the
    double-buffering window in the trainer explicit: staging batch ``k+1``
    can be issued while the step for batch ``k`` is still in flight.
    """
    return (
        jnp.asarray(feats),
        plan_to_device(plan, cache_plan, with_halves, num_replicated),
        jnp.asarray(labels, jnp.int32),
    )


def transfer_counts(staged) -> dict:
    """``bytes`` and ``arrays`` of a staged pytree's leaves: what one
    ``stage_batch`` sent host -> device (the ``step/put`` counters)."""
    leaves = jax.tree_util.tree_leaves(staged)
    return {"bytes": sum(x.nbytes for x in leaves), "arrays": len(leaves)}


def true_feature_rows(plan: SplitPlan, cache_plan: CachePlan | None = None) -> int:
    """True (unpadded) rows of a batch's host feature block: the cache
    misses when served, else the input frontier, summed over devices."""
    mask = plan.node_mask[-1] if cache_plan is None else cache_plan.miss_mask
    return int(mask.sum())


class FeatureBlockPool:
    """Free host feature blocks, kept for reuse and keyed by (shape, dtype).

    ``acquire`` hands out a free block of the key, or a new one when the key
    has none free: it never blocks and never caps. ``release`` takes back a
    block that ``acquire`` handed out, once nothing reads it any more;
    anything else (a padded copy, a second release) is ignored. A block
    that is never released is simply garbage-collected: correctness never
    depends on a release, only reuse does. A reused block holds the last
    batch's rows, so its writer must overwrite every element.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._free: dict[tuple, list[np.ndarray]] = {}
        # blocks handed out and not yet released, by id; weak, so a block
        # dropped without a release is freed (and leaves this map)
        self._held = weakref.WeakValueDictionary()

    def acquire(self, shape: tuple, dtype) -> tuple[np.ndarray, bool]:
        """A block of ``shape`` and ``dtype``, and whether it was reused."""
        key = (tuple(shape), np.dtype(dtype))
        with self._lock:
            free = self._free.get(key)
            reused = bool(free)
            block = free.pop() if reused else self._allocate(*key)
            self._held[id(block)] = block
        return block, reused

    def _allocate(self, shape: tuple, dtype: np.dtype) -> np.ndarray:
        return np.empty(shape, dtype)

    def release(self, block: np.ndarray) -> bool:
        """Take back a block ``acquire`` handed out; False (and nothing
        kept) for any other array."""
        with self._lock:
            if self._held.get(id(block)) is not block:
                return False
            del self._held[id(block)]
            self._free.setdefault((block.shape, block.dtype), []).append(block)
        return True


def _gather_rows(
    features: np.ndarray, ids: np.ndarray, mask: np.ndarray,
    out: np.ndarray | None,
) -> np.ndarray:
    """``features[ids]`` as float32 with the rows ``mask`` leaves out zeroed,
    written into ``out`` (a new array when None). Every element of ``out``
    is written, so a reused block keeps nothing of its last batch."""
    if out is None:
        out = np.empty((*ids.shape, features.shape[1]), np.float32)
    if ids.size and (ids.min() < 0 or ids.max() >= len(features)):
        raise IndexError(
            f"feature ids outside [0, {len(features)}): "
            f"{ids.min()}..{ids.max()}"
        )
    # mode="clip" after the check above: with ``out`` the default
    # mode="raise" buffers the whole output, about 2.7x slower
    np.take(features, ids, axis=0, out=out, mode="clip")
    # zero only the padded rows (they gathered vertex 0's features) instead
    # of multiplying the whole block by the mask — the padded fraction is
    # small, so this roughly halves the memory traffic of the loading stage
    out[~mask] = 0.0
    return out


def load_features(plan: SplitPlan, features: np.ndarray) -> np.ndarray:
    """The *loading* phase: gather input rows per device (dedup'd under split).

    Returns (P, N_L, F) float32; padding rows zeroed.
    """
    return _gather_rows(features, plan.front_ids[-1], plan.node_mask[-1], None)


def load_miss_features(cp: CachePlan, features: np.ndarray) -> np.ndarray:
    """Host gather of only the cache-miss rows: (P, M, F) float32, padding 0.

    This is the whole point of the serving path — the host link carries
    ``M`` rows per device instead of ``N_L``.
    """
    return _gather_rows(features, cp.miss_ids, cp.miss_mask, None)


def stage_host_features(
    plan: SplitPlan,
    features: np.ndarray,
    cache=None,
    serve_cache: bool = False,
    pad_multiple: int = 8,
    pool: FeatureBlockPool | None = None,
) -> tuple:
    """The load stage for one plan: ``(cache_plan, feats, breakdown, reused)``.

    Chooses the serving path (compacted miss gather + CachePlan) or the full
    host gather. The single definition shared by ``PlanProducer.build``
    (producer threads) and ``Trainer.train_iter`` (inline path) — the two
    must stay bit-identical. With a ``pool`` the rows are written into a
    block acquired from it (``reused`` says whether the block was a free
    one); whoever steps the batch releases ``feats`` once the device has
    read it.
    """
    if cache is not None and serve_cache and cache.serves:
        cp = cache.build_plan(plan, pad_multiple=pad_multiple)
        ids, mask, breakdown = cp.miss_ids, cp.miss_mask, cp.breakdown()
    else:
        cp = None
        ids, mask = plan.front_ids[-1], plan.node_mask[-1]
        breakdown = cache.classify_plan(plan) if cache else None
    out, reused = None, False
    if pool is not None:
        out, reused = pool.acquire((*ids.shape, features.shape[1]), np.float32)
    return cp, _gather_rows(features, ids, mask, out), breakdown, reused


def pad_block(
    block: np.ndarray, rows: int, pool: FeatureBlockPool | None
) -> np.ndarray:
    """A staged feature block grown to ``rows`` rows a device (``pad_axis``);
    a pooled block that the grown copy replaces goes back to ``pool``."""
    grown = pad_axis(block, 1, rows)
    if grown is not block and pool is not None:
        pool.release(block)
    return grown


def load_labels(plan: SplitPlan, labels: np.ndarray) -> np.ndarray:
    """Labels of the (local) target rows per device, padding = 0."""
    lab = labels[plan.front_ids[0]]
    return (lab * plan.node_mask[0]).astype(np.int32)
