"""The cooperative sampling loop and its producer-facing facade.

``_sample_device`` is the jit-compiled steady-state path (sim mode: leading
device axis ``P``, the exchange is ``core.shuffle.sim_alltoall``). Per GNN
layer it

  1. expands each split's *locally owned* frontier block with the wavefront
     kernel (``ops.wavefront_expand`` — Pallas or jnp backend, bit-identical),
  2. gathers the drawn edges from the device CSR shard (``shard.py``),
  3. de-duplicates the candidate next frontier per split
     (``frontier.sorted_unique_capped``),
  4. routes newly discovered remote vertices to their owning split through
     the fixed-size all-to-all (``frontier.bucket_by_owner`` builds the
     (P, P, X) send buffer — the §4 cooperative exchange), and
  5. merges received + locally owned candidates into the next frontier.

Every capacity is static (jit signatures bounded by pow2 caps); exceeding
one raises an overflow flag instead of truncating. ``DeviceSampler`` owns
the caps: it calibrates them from one host-sampled batch, doubles a flagged
cap at the next epoch boundary (``refresh_caps`` — *never* mid-epoch, so
serial and pipelined producers see identical caps and the
serial == pipelined contract survives, DESIGN.md §6), and falls back to the
host sampler for the overflowing batch.

``sample_minibatch_spmd`` is the same loop written against one shard for
`shard_map` bodies: the vmapped steps run unbatched and the exchange is
``jax.lax.all_to_all``. ``tests/test_sampler.py`` pins spmd == sim.

Determinism: draws are keyed by ``(seed, epoch, batch, layer, vertex,
slot)`` (``rng.py``), so results are independent of buffer layout, cap
sizes (absent overflow), producer threads, and backend.
"""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.shuffle import sim_alltoall, spmd_alltoall
from repro.graph.csr import CSRGraph
from repro.obs import NULL_OBS
from repro.graph.sampling import (
    LayerSample,
    MiniBatchSample,
    NeighborSampler,
    sample_minibatch,
)
from repro.kernels import pallas_interpret
from repro.kernels.gather_segsum.layout import pow2_at_least
from repro.sampler.frontier import bucket_by_owner, sorted_unique_capped
from repro.sampler.ops import wavefront_expand
from repro.sampler.ref import INVALID, SELF_LOOP
from repro.sampler.rng import fold_key_pair
from repro.sampler.shard import GraphShards, build_shards, shards_to_device

LAYER_SALT = 0x5A3D  # keyspace tag for per-layer draw keys
CALIB_SALT = 0xCA11B  # throwaway stream for capacity calibration


def _decode_edges(front, start, codes, indices, edge_id, e_cap):
    """Slot codes -> (dst, src, eid, valid) edge arrays, flattened per shard.

    ``front``/``start`` are (N,) per-vertex blocks, ``codes`` (N, fanout)
    from the wavefront kernel; ``indices``/``edge_id`` the shard's (E_cap,)
    CSR payload. Self-loop codes read no CSR slot; invalid codes are masked.
    """
    N, fanout = codes.shape
    off = jnp.maximum(codes, 0)
    eidl = jnp.clip(start[:, None] + off, 0, e_cap - 1)
    src = indices[eidl]
    eid = edge_id[eidl]
    dst = jnp.broadcast_to(front[:, None], (N, fanout))
    is_self = codes == SELF_LOOP
    src = jnp.where(is_self, dst, src)
    eid = jnp.where(is_self, -1, eid)
    valid = codes != INVALID
    return (
        dst.reshape(-1),
        src.reshape(-1),
        eid.reshape(-1),
        valid.reshape(-1),
    )


@functools.partial(
    jax.jit, static_argnames=("caps", "fanouts", "backend", "interpret")
)
def _sample_device(
    dev: dict,  # shards_to_device pytree
    targets: jnp.ndarray,  # (B,) int32, zero-padded
    n_targets: jnp.ndarray,  # () int32 true target count
    layer_keys: jnp.ndarray,  # (L, 2) uint32 folded 64-bit layer keys
    *,
    caps: tuple,  # sorted (name, size) pairs — static
    fanouts: tuple,
    backend: str,
    interpret: bool,
):
    """One mini-batch of cooperative sampling (sim mode, fully on device).

    Returns ``(fronts, counts, layers, flags)``: per-depth (P, N_d) sorted
    frontier blocks + true counts, per-layer flattened edge arrays, and the
    per-capacity overflow flags.
    """
    caps = dict(caps)
    P = dev["indptr"].shape[0]
    V = dev["owner"].shape[0]
    e_cap = dev["indices"].shape[1]
    B = targets.shape[0]

    tvalid = jnp.arange(B) < n_targets
    front, cnt, of0 = bucket_by_owner(
        targets, tvalid, dev["owner"], P, caps["N0"], V
    )
    fronts, counts, layers = [front], [cnt], []
    flags = {"N0": of0}
    for l, fanout in enumerate(fanouts):
        N = caps[f"N{l}"]
        front, cnt = fronts[-1], counts[-1]
        fvalid = jnp.arange(N)[None, :] < cnt[:, None]
        lr = dev["local_row"][jnp.clip(front, 0, V - 1)]  # (P, N)
        start = jnp.take_along_axis(dev["indptr"], lr, axis=1)
        deg = jnp.take_along_axis(dev["indptr"], lr + 1, axis=1) - start
        deg = jnp.where(fvalid, deg, -1)
        # one flat kernel launch for all P splits — draws key on global
        # vertex id, so flattening the device axis is invisible to them
        codes = wavefront_expand(
            front.reshape(-1),
            deg.reshape(-1),
            layer_keys[l],
            fanout,
            backend=backend,
            interpret=interpret,
        ).reshape(P, N, fanout)
        dst, src, eid, evalid = jax.vmap(
            lambda f, s, c, i, e: _decode_edges(f, s, c, i, e, e_cap)
        )(front, start, codes, dev["indices"], dev["edge_id"])
        layers.append({"dst": dst, "src": src, "eid": eid, "valid": evalid})

        # --- cooperative frontier advance -------------------------------
        C, X, N1 = caps[f"C{l}"], caps[f"X{l}"], caps[f"N{l + 1}"]
        cand = jnp.concatenate([src, front], axis=1)
        cvalid = jnp.concatenate([evalid, fvalid], axis=1)
        uniq, ucnt, ofc = jax.vmap(
            lambda v, m: sorted_unique_capped(v, m, C, V)
        )(cand, cvalid)
        uvalid = jnp.arange(C)[None, :] < ucnt[:, None]
        mine = dev["owner"][jnp.clip(uniq, 0, V - 1)] == jnp.arange(P)[:, None]
        send, scnt, ofx = jax.vmap(
            lambda v, m: bucket_by_owner(v, m, dev["owner"], P, X, V)
        )(uniq, uvalid & ~mine)
        # the frontier exchange rides the same wire choke point as the layer
        # shuffles / cache fetch (core.shuffle); its payload is integer
        # vertex ids, which ``wire_cast``'s int guard exempts from any
        # configured down-cast — ids must never be quantized
        recv = sim_alltoall(send)  # (P, P, X): recv[q, p] = p's block for q
        rcnt = scnt.T
        rvalid = jnp.arange(X)[None, None, :] < rcnt[:, :, None]
        merged = jnp.concatenate([uniq, recv.reshape(P, P * X)], axis=1)
        mvalid = jnp.concatenate(
            [uvalid & mine, rvalid.reshape(P, P * X)], axis=1
        )
        nf, ncnt, ofn = jax.vmap(
            lambda v, m: sorted_unique_capped(v, m, N1, V)
        )(merged, mvalid)
        flags[f"C{l}"] = jnp.any(ofc)
        flags[f"X{l}"] = jnp.any(ofx)
        flags[f"N{l + 1}"] = jnp.any(ofn)
        fronts.append(nf)
        counts.append(ncnt)
    return fronts, counts, layers, flags


def sample_minibatch_spmd(
    dev_local: dict,  # per-shard slices: indptr (V_cap+1,), indices/edge_id
    #                   (E_cap,); owner/local_row (V,) replicated
    targets: jnp.ndarray,  # (B,) int32 full target list (replicated)
    n_targets: jnp.ndarray,  # () int32
    layer_keys: jnp.ndarray,  # (L, 2) uint32
    *,
    caps: tuple,
    fanouts: tuple,
    axis_name: str,
    num_parts: int,  # static mesh-axis size (sizes the exchange buffers)
    backend: str = "jnp",
):
    """The cooperative loop for one shard inside a `shard_map` body.

    Identical math to ``_sample_device`` — the vmapped steps run unbatched
    on this shard's frontier and the exchange is ``jax.lax.all_to_all``
    (send counts ride their own all-to-all to mask the receive side).
    ``axis_name`` is the mesh's *split* axis: on the 2D (replica, split)
    mesh of ``launch.sharding.make_split_mesh`` the frontier exchange and
    ``axis_index`` resolve within this device's replica group only, so R
    replica groups cooperatively sample R independent mini-batches from one
    program (``num_parts`` stays P, the split-axis size — never R*P).
    Returns this shard's ``(fronts, counts, layers, flags)``; the flags are
    this shard's overflow indicators per capacity key — callers must
    ``jnp.any`` them across shards (or check each shard's) and discard the
    batch on overflow, exactly like the sim driver's fallback: a flagged
    output is truncated and must not be consumed as a sample.
    """
    caps = dict(caps)
    P = num_parts
    p = jax.lax.axis_index(axis_name)
    V = dev_local["owner"].shape[0]
    e_cap = dev_local["indices"].shape[0]
    B = targets.shape[0]

    tvalid = (jnp.arange(B) < n_targets) & (
        dev_local["owner"][jnp.clip(targets, 0, V - 1)] == p
    )
    front, cnt, of0 = sorted_unique_capped(targets, tvalid, caps["N0"], V)
    fronts, counts, layers = [front], [cnt], []
    flags = {"N0": of0}
    for l, fanout in enumerate(fanouts):
        N = caps[f"N{l}"]
        front, cnt = fronts[-1], counts[-1]
        fvalid = jnp.arange(N) < cnt
        lr = dev_local["local_row"][jnp.clip(front, 0, V - 1)]
        start = dev_local["indptr"][lr]
        deg = dev_local["indptr"][lr + 1] - start
        deg = jnp.where(fvalid, deg, -1)
        codes = wavefront_expand(
            front, deg, layer_keys[l], fanout,
            backend=backend,
            interpret=backend == "pallas" and pallas_interpret(),
        )
        dst, src, eid, evalid = _decode_edges(
            front, start, codes, dev_local["indices"], dev_local["edge_id"],
            e_cap,
        )
        layers.append({"dst": dst, "src": src, "eid": eid, "valid": evalid})

        C, X, N1 = caps[f"C{l}"], caps[f"X{l}"], caps[f"N{l + 1}"]
        cand = jnp.concatenate([src, front])
        cvalid = jnp.concatenate([evalid, fvalid])
        uniq, ucnt, ofc = sorted_unique_capped(cand, cvalid, C, V)
        uvalid = jnp.arange(C) < ucnt
        mine = dev_local["owner"][jnp.clip(uniq, 0, V - 1)] == p
        send, scnt, ofx = bucket_by_owner(
            uniq, uvalid & ~mine, dev_local["owner"], P, X, V
        )
        recv = spmd_alltoall(send, axis_name)  # (P, X) — int ids, exempt
        rcnt = spmd_alltoall(scnt[:, None], axis_name).reshape(P)
        rvalid = jnp.arange(X)[None, :] < rcnt[:, None]
        merged = jnp.concatenate([uniq, recv.reshape(-1)])
        mvalid = jnp.concatenate([uvalid & mine, rvalid.reshape(-1)])
        nf, ncnt, ofn = sorted_unique_capped(merged, mvalid, N1, V)
        flags[f"C{l}"] = ofc
        flags[f"X{l}"] = ofx
        flags[f"N{l + 1}"] = ofn
        fronts.append(nf)
        counts.append(ncnt)
    return fronts, counts, layers, flags


class DeviceSampler:
    """Producer-facing facade: device sampling with host-sampler fallback.

    Thread-safe for the pipelined runtime: any producer thread may call
    ``sample_batch`` for any ``(epoch, batch)``. Shared mutable state is
    limited to the capacity table and counters, and caps only change inside
    ``refresh_caps`` (called by the plan source at epoch boundaries), so the
    set of batches that overflow — and therefore fall back — is a pure
    function of ``(seed, epoch)``, independent of thread scheduling.
    """

    def __init__(
        self,
        graph: CSRGraph,
        assignment: np.ndarray,
        num_devices: int,
        fanouts: list[int],
        seed: int,
        host_sampler: NeighborSampler,
        backend: str = "pallas",
        headroom: float = 1.5,
    ):
        self.graph = graph
        self.fanouts = tuple(int(f) for f in fanouts)
        self.seed = seed
        self.host = host_sampler
        self.backend = backend
        # the Pallas kernel interprets on the CPU and compiles on a TPU
        self.interpret = backend == "pallas" and pallas_interpret()
        self.shards: GraphShards = build_shards(
            graph, np.asarray(assignment), num_devices
        )
        self._dev = shards_to_device(self.shards)
        self._lock = threading.Lock()
        self.batches = 0
        self.fallbacks = 0
        self._epoch_base = (0, 0)  # (batches, fallbacks) at last refresh
        self.hwm: dict[str, int] = {}
        self._pending: dict[str, int] = {}
        self._caps = self._calibrate(headroom)
        # tracing/metrics sink; the trainer re-points this at its own Obs
        self.obs = NULL_OBS

    @property
    def num_devices(self) -> int:
        return self.shards.num_parts

    # ------------------------------------------------------------------ #
    def _cap(self, x: float, limit: int | None = None) -> int:
        c = pow2_at_least(max(int(np.ceil(x)), 1), floor=16)
        if limit is not None:
            c = min(c, pow2_at_least(limit, floor=16))
        return c

    def _calibrate(self, headroom: float) -> dict[str, int]:
        """Size the static caps from one host-sampled batch (+ headroom).

        A deliberate underestimate is safe — an overflowing batch falls back
        to the host sampler and the cap doubles at the next epoch boundary —
        so one representative batch with modest headroom converges within an
        epoch or two instead of over-padding every buffer.
        """
        P = self.num_devices
        owner = self.shards.owner
        targets = np.asarray(self.host.train_ids[: self.host.batch_size])
        mb = sample_minibatch(
            self.graph, targets, list(self.fanouts),
            np.random.default_rng((self.seed, CALIB_SALT)),
        )
        caps: dict[str, int] = {}
        for d, fr in enumerate(mb.frontiers):
            per_dev = np.bincount(owner[fr], minlength=P)
            caps[f"N{d}"] = self._cap(
                per_dev.max(initial=1) * headroom, limit=self.shards.v_cap
            )
        for l, layer in enumerate(mb.layers):
            dst_o = owner[layer.dst]
            c_max, x_max = 1, 1
            for p in range(P):
                srcs = layer.src[dst_o == p]
                local_front = mb.frontiers[l][owner[mb.frontiers[l]] == p]
                cand = np.unique(np.concatenate([srcs, local_front]))
                c_max = max(c_max, cand.size)
                remote = np.unique(srcs[owner[srcs] != p])
                if remote.size:
                    x_max = max(
                        x_max,
                        int(np.bincount(owner[remote], minlength=P).max()),
                    )
            caps[f"C{l}"] = self._cap(c_max * headroom)
            caps[f"X{l}"] = self._cap(x_max * headroom)
        return caps

    # ------------------------------------------------------------------ #
    def caps_tuple(self) -> tuple:
        """The current caps as the static jit key (sorted name/size pairs)."""
        with self._lock:
            return tuple(sorted(self._caps.items()))

    def layer_keys(self, epoch: int, batch: int) -> np.ndarray:
        """Folded per-layer 64-bit draw keys for one batch (uint32, (L, 2))."""
        return np.array(
            [
                fold_key_pair(self.seed, LAYER_SALT, epoch, batch, l)
                for l in range(len(self.fanouts))
            ],
            dtype=np.uint32,
        )

    def sample_batch(
        self,
        targets: np.ndarray,
        epoch: int,
        batch: int,
        replica: int = 0,
        num_replicas: int = 1,
    ) -> MiniBatchSample:
        """Sample one mini-batch on device, keyed by ``(seed, epoch, batch)``.

        On capacity overflow the batch is re-sampled by the host sampler's
        keyed API (identical call the pure-host producer would make) and the
        flagged caps are scheduled to double at the next ``refresh_caps``.

        On the 2D mesh each replica group samples its own chunk of the
        global batch: ``(replica, num_replicas)`` fold into the draw keys
        via the flattened batch counter ``batch * num_replicas + replica``,
        so the R per-replica streams are disjoint but each remains a pure
        function of static integers (the keyed-RNG discipline, DESIGN.md
        §6). The defaults ``(0, 1)`` leave the key exactly as before — the
        1D path is byte-identical.
        """
        if not (0 <= replica < max(num_replicas, 1)):
            raise ValueError(
                f"replica {replica} out of range for R={num_replicas}"
            )
        key_batch = batch * max(num_replicas, 1) + replica
        targets = np.asarray(targets, dtype=np.int64)
        caps = self.caps_tuple()
        B = pow2_at_least(max(targets.shape[0], 1), floor=16)
        tpad = np.zeros(B, np.int32)
        tpad[: targets.shape[0]] = targets
        out = _sample_device(
            self._dev,
            jnp.asarray(tpad),
            jnp.int32(targets.shape[0]),
            jnp.asarray(self.layer_keys(epoch, key_batch)),
            caps=caps,
            fanouts=self.fanouts,
            backend=self.backend,
            interpret=self.interpret,
        )
        fronts, counts, layers, flags = jax.device_get(out)
        overflowed = sorted(k for k, f in flags.items() if bool(f))
        with self._lock:
            self.batches += 1
            for d, c in enumerate(counts):
                k = f"N{d}"
                self.hwm[k] = max(self.hwm.get(k, 0), int(c.max(initial=0)))
            if overflowed:
                self.fallbacks += 1
                for k in overflowed:
                    self._pending[k] = max(
                        self._pending.get(k, 0), 2 * dict(caps)[k]
                    )
        if overflowed:
            # the fallback is benign (identical keyed draw on the host) but
            # must never be *silent*: it means caps were undersized and the
            # batch paid the host-sampling price
            self.obs.count("fault/sampler_fallback", 1)
            self.obs.instant(
                "fault/sampler_fallback",
                {"epoch": epoch, "batch": key_batch, "caps": overflowed},
            )
            return self.host.sample_batch(targets, epoch, key_batch)
        return self._assemble(targets, fronts, counts, layers)

    def _assemble(self, targets, fronts, counts, layers) -> MiniBatchSample:
        """Device blocks -> the host ``MiniBatchSample`` plan input.

        Per-device frontier blocks are sorted and disjoint (each vertex
        lives only on its owner), so the global sorted-unique frontier is a
        sort of their concatenation.
        """
        P = self.num_devices
        frontiers = []
        for f, c in zip(fronts, counts):
            sel = np.concatenate([f[p, : c[p]] for p in range(P)])
            frontiers.append(np.sort(sel).astype(np.int64))
        out_layers = []
        for l in layers:
            m = l["valid"].astype(bool)
            out_layers.append(
                LayerSample(
                    src=l["src"][m].astype(np.int64),
                    dst=l["dst"][m].astype(np.int64),
                    edge_id=l["eid"][m].astype(np.int64),
                )
            )
        return MiniBatchSample(
            target_ids=targets, layers=out_layers, frontiers=frontiers
        )

    # ------------------------------------------------------------------ #
    def refresh_caps(self) -> None:
        """Apply pending capacity growth (epoch boundaries only — growing
        mid-epoch would make fallback decisions order-dependent). Also
        snapshots the batch/fallback counters so ``stats`` can report
        honest per-epoch deltas alongside the run totals."""
        with self._lock:
            for k, v in self._pending.items():
                self._caps[k] = max(self._caps[k], v)
            self._pending.clear()
            self._epoch_base = (self.batches, self.fallbacks)

    def export_state(self) -> dict:
        """JSON-able capacity/counter state for the checkpoint cursor.

        Caps, pending growth, and the fallback bookkeeping are part of the
        resume contract in device mode: which batches overflow (and so fall
        back to the host sampler) depends on the capacity table, so a
        bit-exact resume must restore it rather than recalibrate.
        """
        with self._lock:
            return {
                "caps": {k: int(v) for k, v in self._caps.items()},
                "pending": {k: int(v) for k, v in self._pending.items()},
                "hwm": {k: int(v) for k, v in self.hwm.items()},
                "batches": int(self.batches),
                "fallbacks": int(self.fallbacks),
                "epoch_base": list(self._epoch_base),
            }

    def load_state(self, state: dict) -> None:
        """Restore ``export_state`` output (checkpoint resume)."""
        with self._lock:
            self._caps = {k: int(v) for k, v in state["caps"].items()}
            self._pending = {k: int(v) for k, v in state["pending"].items()}
            self.hwm = {k: int(v) for k, v in state["hwm"].items()}
            self.batches = int(state["batches"])
            self.fallbacks = int(state["fallbacks"])
            self._epoch_base = tuple(int(x) for x in state["epoch_base"])

    def stats(self) -> dict:
        """Counters + capacity state. ``sampler_batches``/``sampler_fallbacks``
        are run-cumulative; the ``sampler_epoch_*`` pair counts since the
        last ``refresh_caps`` (i.e. the current epoch under the device plan
        sources) — use those for per-epoch rates."""
        with self._lock:
            b0, f0 = self._epoch_base
            return {
                "sampler_batches": self.batches,
                "sampler_fallbacks": self.fallbacks,
                "sampler_epoch_batches": self.batches - b0,
                "sampler_epoch_fallbacks": self.fallbacks - f0,
                "sampler_caps": dict(self._caps),
                "sampler_hwm": dict(self.hwm),
            }
