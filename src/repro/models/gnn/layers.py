"""GNN layers behind the paper's layer-centric API (§6).

Each layer is a "single-GPU kernel used as a black box": it consumes the
*mixed frontier* buffer (local + received rows, built by the shuffle) and
per-edge indices, and produces the local rows of the next depth. The same
function serves split-parallel, data-parallel, and single-device execution —
only the shuffle that builds ``mixed`` differs (paper's Algorithm 2).

Supported models: GraphSAGE (mean), GAT (multi-head attention), GCN.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core.shuffle import (
    SimComm,
    SpmdComm,
    chunk_slices,
    sim_append_replicated,
    spmd_append_replicated,
)
from repro.kernels import pallas_interpret, segment_ops
from repro.kernels.gather_segsum import ops as gather_ops


@dataclass(frozen=True)
class GNNSpec:
    model: str = "sage"  # sage | gat | gcn
    in_dim: int = 128
    hidden_dim: int = 256  # paper default 256
    out_dim: int = 16
    num_layers: int = 3  # paper default 3
    num_heads: int = 4  # GAT only
    # Aggregation backend (docs/KERNELS.md). "jnp" materializes the (E, F)
    # per-edge buffer + XLA scatter-add; "pallas" runs the fused
    # gather->segment-aggregate kernels over the plan's dst-sorted layout.
    agg_backend: str = "jnp"  # jnp | pallas
    # Overlap-aware shuffle schedule (DESIGN.md §3a). ``overlap`` switches
    # the per-layer step from blocking shuffle->aggregate to split
    # aggregation: the local-src half is aggregated from the device's own
    # rows while the all-to-all for the remote-src half is in flight.
    # ``shuffle_chunks`` tiles that all-to-all along the feature axis so
    # chunk k+1's exchange can fly while chunk k's remote partial
    # aggregation runs. ``wire_dtype`` down-casts only the rows on the wire
    # (fp32 accumulation everywhere); fp32 wire is bit-exact.
    overlap: bool = False
    shuffle_chunks: int = 1
    wire_dtype: str = "float32"  # float32 | bfloat16 | float16
    dtype: str = "float32"

    def layer_dims(self) -> list[tuple[int, int]]:
        dims = []
        d_in = self.in_dim
        for i in range(self.num_layers):
            d_out = self.out_dim if i == self.num_layers - 1 else self.hidden_dim
            dims.append((d_in, d_out))
            d_in = d_out
        return dims


def _glorot(key, shape, dtype):
    fan_in, fan_out = shape[-2], shape[-1]
    lim = float(np.sqrt(6.0 / (fan_in + fan_out)))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def init_gnn_params(key: jax.Array, spec: GNNSpec) -> list[dict]:
    dtype = jnp.dtype(spec.dtype)
    params = []
    for i, (d_in, d_out) in enumerate(spec.layer_dims()):
        key, k1, k2, k3, k4 = jax.random.split(key, 5)
        if spec.model == "sage":
            params.append(
                {
                    "w_self": _glorot(k1, (d_in, d_out), dtype),
                    "w_neigh": _glorot(k2, (d_in, d_out), dtype),
                    "b": jnp.zeros((d_out,), dtype),
                }
            )
        elif spec.model == "gcn":
            params.append(
                {
                    "w": _glorot(k1, (d_in, d_out), dtype),
                    "b": jnp.zeros((d_out,), dtype),
                }
            )
        elif spec.model == "gat":
            H = spec.num_heads
            dh = d_out // H
            assert dh * H == d_out, "gat: out dim must divide num_heads"
            params.append(
                {
                    "w": _glorot(k1, (d_in, H, dh), dtype),
                    "a_src": _glorot(k2, (1, H, dh), dtype)[0],
                    "a_dst": _glorot(k3, (1, H, dh), dtype)[0],
                    "b": jnp.zeros((d_out,), dtype),
                }
            )
        else:
            raise ValueError(f"unknown GNN model {spec.model!r}")
    return params


def _scoped(name: str):
    """Run the decorated function under ``jax.named_scope(name)`` (a fresh
    scope per call: one shared scope object is not safe across threads)."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


# Every aggregation runs under the ``agg`` scope, whichever backend
# implements it, so one name in the trace reads aggregation time.
@_scoped("agg")
def _agg_mean(spec: GNNSpec, mixed: jnp.ndarray, lp: dict, num_out: int):
    """Masked mean of ``mixed[edge_src]`` per destination, backend-dispatched.

    ``pallas`` runs the fused gather->segment-mean kernel on the plan's
    dst-sorted layout — the (E, F) per-edge buffer is never materialized and
    the denominator comes from the plan's CSR offsets. ``jnp`` is the
    reference two-op path (gather, then XLA scatter-add).
    """
    if spec.agg_backend == "pallas":
        return gather_ops.gather_segment_mean(
            mixed, lp["edge_src"], lp["pack_perm"], lp["pack_dst"],
            lp["seg_offsets"], num_out, interpret=pallas_interpret(),
        )
    h_src = mixed[lp["edge_src"]]  # (E, F_in) — the buffer pallas avoids
    return segment_ops.segment_mean(
        h_src, lp["edge_dst"], lp["edge_mask"], num_out
    )


@_scoped("agg")
def _agg_weighted_sum(
    spec: GNNSpec, mixed_flat: jnp.ndarray, alpha: jnp.ndarray, lp: dict,
    num_out: int,
):
    """GAT aggregation: sum of alpha[e, h] * mixed[src, head h's columns]."""
    if spec.agg_backend == "pallas":
        return gather_ops.gather_weighted_segsum(
            mixed_flat, alpha, lp["edge_src"], lp["pack_perm"],
            lp["pack_dst"], num_out, interpret=pallas_interpret(),
        )
    E, H = alpha.shape
    dh = mixed_flat.shape[1] // H
    msg = mixed_flat[lp["edge_src"]].reshape(E, H, dh) * alpha[:, :, None]
    return segment_ops.segment_sum(
        msg.reshape(E, H * dh), lp["edge_dst"], lp["edge_mask"], num_out
    )


def gnn_layer_apply(
    spec: GNNSpec,
    layer_params: dict,
    mixed: jnp.ndarray,  # (M, F_in) mixed-frontier rows (local + received)
    lp: dict,  # one device's LayerPlan arrays (see plan_io.plan_to_device)
    num_out: int,
    is_last: bool,
) -> jnp.ndarray:
    """One GNN layer on one device (the layer-centric 'black box' kernel).

    ``lp`` carries both addressings of the same edge set: the edge-order
    arrays (``edge_src``/``edge_dst``/``edge_mask``) used by the jnp backend
    and the dst-sorted packed layout (``pack_perm``/``pack_dst``/
    ``seg_offsets``) used by the fused Pallas backend — docs/KERNELS.md.
    """
    edge_src, edge_dst = lp["edge_src"], lp["edge_dst"]
    edge_mask, self_pos = lp["edge_mask"], lp["self_pos"]
    if spec.model == "sage":
        agg = _agg_mean(spec, mixed, lp, num_out)
        h_self = mixed[self_pos]
        out = h_self @ layer_params["w_self"] + agg @ layer_params["w_neigh"]
        out = out + layer_params["b"]
    elif spec.model == "gcn":
        agg = _agg_mean(spec, mixed, lp, num_out)
        out = agg @ layer_params["w"] + layer_params["b"]
    elif spec.model == "gat":
        w = layer_params["w"]  # (F_in, H, dh)
        H, dh = w.shape[1], w.shape[2]
        wh = jnp.einsum("mf,fhd->mhd", mixed, w)  # (M, H, dh)
        s_src = jnp.einsum("mhd,hd->mh", wh, layer_params["a_src"])  # (M, H)
        # dst-order scores, computed once per layer: destinations are local
        # rows (``self_pos``), so only N_i of the M mixed rows ever
        # contribute an a_dst score. Scoring ``wh[self_pos]`` directly is
        # bit-identical per row to the old full (M, H) score table and
        # replaces the chained dependent gathers ``s_dst[self_pos][edge_dst]``
        # with one (N_i, H) table and a single (E, H) gather.
        s_dst_n = jnp.einsum(
            "nhd,hd->nh", wh[self_pos], layer_params["a_dst"]
        )  # (N_i, H)
        logits = jax.nn.leaky_relu(
            s_src[edge_src] + s_dst_n[edge_dst], negative_slope=0.2
        )  # (E, H)
        # softmax normalization stays on the (E, H) jnp path in both
        # backends: it is H/dh-times smaller than the feature traffic, and
        # keeping one implementation makes the backends agree on alpha
        # bit-for-bit (only the weighted sum below differs, by fp tolerance)
        with jax.named_scope("softmax"):
            alpha = segment_ops.edge_softmax(
                logits, edge_dst, edge_mask, num_out
            )  # (E, H)
        agg = _agg_weighted_sum(
            spec, wh.reshape(wh.shape[0], H * dh), alpha, lp, num_out
        )
        out = agg + layer_params["b"]
    else:
        raise ValueError(spec.model)
    if not is_last:
        out = jax.nn.relu(out)
    return out


@_scoped("agg")
def _half_sum(spec: GNNSpec, rows: jnp.ndarray, lp: dict, side: str,
              num_out: int) -> jnp.ndarray:
    """Per-device partial sum over one edge half (``side`` in {"l", "r"}).

    ``rows`` is the half's source space: the local row block for "l", the
    recv region for "r" (half ``*edge_src`` entries index it directly). A
    zero-width half (static) contributes exact zeros — the all-local dp
    plan and the no-cross-edges batch both hit this path.
    """
    src = lp[f"{side}edge_src"]
    if src.shape[0] == 0:
        return jnp.zeros((num_out, rows.shape[-1]), rows.dtype)
    if spec.agg_backend == "pallas":
        return gather_ops.gather_segment_sum(
            rows, src, lp[f"{side}pack_perm"], lp[f"{side}pack_dst"],
            num_out, interpret=pallas_interpret(),
        )
    h_src = rows[src]
    return segment_ops.segment_sum(
        h_src, lp[f"{side}edge_dst"], lp[f"{side}edge_mask"], num_out
    )


@_scoped("agg")
def _half_weighted(spec: GNNSpec, rows: jnp.ndarray, alpha_half: jnp.ndarray,
                   lp: dict, side: str, num_out: int, dh: int) -> jnp.ndarray:
    """Per-device weighted partial sum over one edge half (GAT).

    ``rows (R, Hc*dh)`` carries whole heads (chunk boundaries are
    dh-aligned); ``alpha_half (EW, Hc)`` is the half's attention weights
    sliced to the chunk's heads. Padding slots are killed by the half mask
    (jnp) or the pack sentinel (pallas), so stale alpha values at masked
    positions are never read.
    """
    src = lp[f"{side}edge_src"]
    if src.shape[0] == 0:
        return jnp.zeros((num_out, rows.shape[-1]), rows.dtype)
    if spec.agg_backend == "pallas":
        return gather_ops.gather_weighted_segsum(
            rows, alpha_half, src, lp[f"{side}pack_perm"],
            lp[f"{side}pack_dst"], num_out, interpret=pallas_interpret(),
        )
    E, Hc = alpha_half.shape
    msg = rows[src].reshape(E, Hc, dh) * alpha_half[:, :, None]
    return segment_ops.segment_sum(
        msg.reshape(E, Hc * dh), lp[f"{side}edge_dst"],
        lp[f"{side}edge_mask"], num_out,
    )


def _gnn_layer_overlap(
    spec: GNNSpec,
    layer_params: dict,
    h: jnp.ndarray,  # (P, N, F) sim / (N, F) spmd — local rows, depth i+1
    lp: dict,  # LayerPlan arrays (leading P axis in sim, sliced in spmd)
    num_out: int,
    is_last: bool,
    comm,  # core.shuffle.SimComm | SpmdComm
    rep_block: jnp.ndarray | None = None,  # (R, F) replicated input rows
) -> jnp.ndarray:
    """One GNN layer under the overlap schedule (DESIGN.md §3a).

    Split aggregation: the local-src half of the edge set is aggregated
    from the device's own row block while the all-to-all for the remote
    half is in flight; the exchange is tiled along the feature axis
    (``spec.shuffle_chunks``) so chunk k+1 flies while chunk k's remote
    partial aggregation runs, and rows travel in ``spec.wire_dtype``
    (fp32 accumulation throughout). Numerics: equal to the blocking
    ``gnn_layer_apply`` within fp tolerance (partial sums reassociate the
    edge reduction); bit-stable across serial/pipelined delivery.

    GAT note: the overlapped schedule exchanges *transformed* rows
    (``wh = h @ w``, computed on the owner — parameters are replicated)
    plus an eager exchange of the (N, H) a_src scores, so attention
    weights for all edges are available before any feature chunk lands and
    every chunk's remote partial depends only on its own recv block.

    ``rep_block`` (input layer only) carries the statically replicated
    feature rows: the plan's local half addresses the source space
    ``concat([local rows, replicated rows])``, so the block is appended to
    the local half's rows (``comm.append_rows`` — a broadcast, no wire
    traffic) and replicated-src edges aggregate in the local partial while
    the (now smaller) remote exchange flies. For GAT the block is
    transformed and scored on device exactly like local rows.
    """
    wire = spec.wire_dtype
    send_idx = lp["send_idx"]
    lp_v = {k: v for k, v in lp.items() if k != "send_idx"}
    S = send_idx.shape[-1]
    B = comm.vmap

    if spec.model in ("sage", "gcn"):
        payload = h  # rows travel as raw features, like the blocking path
        pay_rep = rep_block  # raw features for replicated rows too
        align = 1
    elif spec.model == "gat":
        w = layer_params["w"]  # (F_in, H, dh)
        H, dh = w.shape[1], w.shape[2]
        wh = jnp.einsum("...nf,fhd->...nhd", h, w)
        payload = wh.reshape(*wh.shape[:-2], H * dh)
        if rep_block is not None:
            wh_rep = jnp.einsum("rf,fhd->rhd", rep_block, w)  # (R, H, dh)
            pay_rep = wh_rep.reshape(wh_rep.shape[0], H * dh)
        else:
            pay_rep = None
        align = dh
    else:
        raise ValueError(spec.model)
    F = payload.shape[-1]
    slices = chunk_slices(F, spec.shuffle_chunks, align)
    has_remote = S > 0 and lp["redge_src"].shape[-1] > 0
    send = comm.send_gather(payload, send_idx) if S > 0 else None
    loc_rows = (
        comm.append_rows(payload, pay_rep) if pay_rep is not None else payload
    )

    def _zeros_like_agg():
        return jnp.zeros(payload.shape[:-2] + (num_out, F), payload.dtype)

    if spec.model in ("sage", "gcn"):
        loc = B(lambda hh, l: _half_sum(spec, hh, l, "l", num_out))(
            loc_rows, lp_v
        )
        if has_remote:
            parts = []
            for sl in slices:
                recv = comm.exchange(send[..., sl], wire)
                parts.append(
                    B(lambda rv, l: _half_sum(spec, rv, l, "r", num_out))(
                        recv, lp_v
                    )
                )
            rem = jnp.concatenate(parts, axis=-1)
        else:
            rem = _zeros_like_agg()

        def _finish(lo, re, l, hh):
            count = (l["seg_offsets"][1:] - l["seg_offsets"][:-1]).astype(
                lo.dtype
            )
            agg = (lo + re) / jnp.maximum(count, 1.0)[:, None]
            if spec.model == "sage":
                return (
                    hh[l["self_pos"]] @ layer_params["w_self"]
                    + agg @ layer_params["w_neigh"]
                    + layer_params["b"]
                )
            return agg @ layer_params["w"] + layer_params["b"]

        out = B(_finish)(loc, rem, lp_v, h)
    else:  # gat
        s_src_loc = jnp.einsum("...nhd,hd->...nh", wh, layer_params["a_src"])
        if S > 0:
            # eager score exchange: H columns per row vs H*dh for features —
            # the small price that lets every feature chunk aggregate
            # independently (alpha is feature-independent)
            s_recv = comm.exchange(
                comm.send_gather(s_src_loc, send_idx), wire
            )
            s_src_mix = jnp.concatenate([s_src_loc, s_recv], axis=-2)
        else:
            s_src_mix = s_src_loc
        if pay_rep is not None:
            # replicated rows sit past the recv region in the mixed source
            # space; their a_src scores are computed on device like local rows
            s_rep = jnp.einsum("rhd,hd->rh", wh_rep, layer_params["a_src"])
            s_src_mix = comm.append_rows(s_src_mix, s_rep)

        def _alpha(ssrc, whd, l):
            s_dst_n = jnp.einsum(
                "nhd,hd->nh", whd[l["self_pos"]], layer_params["a_dst"]
            )
            logits = jax.nn.leaky_relu(
                ssrc[l["edge_src"]] + s_dst_n[l["edge_dst"]],
                negative_slope=0.2,
            )
            with jax.named_scope("softmax"):
                return segment_ops.edge_softmax(
                    logits, l["edge_dst"], l["edge_mask"], num_out
                )

        alpha = B(_alpha)(s_src_mix, wh, lp_v)  # (..., E, H)

        def _loc_w(pl, a, l):
            return _half_weighted(
                spec, pl, a[l["ledge_ids"]], l, "l", num_out, dh
            )

        loc = B(_loc_w)(loc_rows, alpha, lp_v)
        if has_remote:
            parts = []
            for sl in slices:
                recv = comm.exchange(send[..., sl], wire)
                hs = slice(sl.start // dh, sl.stop // dh)

                def _rem_w(rv, a, l, hs=hs):
                    return _half_weighted(
                        spec, rv, a[l["redge_ids"]][:, hs], l, "r", num_out,
                        dh,
                    )

                parts.append(B(_rem_w)(recv, alpha, lp_v))
            rem = jnp.concatenate(parts, axis=-1)
        else:
            rem = _zeros_like_agg()
        out = loc + rem + layer_params["b"]
    if not is_last:
        out = jax.nn.relu(out)
    return out


def gnn_forward(
    spec: GNNSpec,
    params: list[dict],
    h_input: jnp.ndarray,  # (P, N_L, F_in) loaded input features per device
    plan_arrays: dict,  # device pytree from repro.train.plan_io.plan_to_device
    shuffle_fn,  # callable(h, send_idx, wire_dtype) -> mixed, e.g.
    #   core.shuffle.sim_shuffle (wire_dtype is always passed — a custom
    #   shuffle_fn must accept it, even if only to ignore it)
    rep_block: jnp.ndarray | None = None,  # (R, F_in) replicated input rows
) -> jnp.ndarray:
    """Split-parallel forward pass (Algorithm 2): shuffle -> gnn_layer, per depth.

    Runs depths L-1 .. 0; returns (P, N_0, out_dim) target logits.
    ``plan_arrays['layers']`` is ordered by dst depth (0 = targets), so we
    iterate it reversed. With ``spec.overlap`` each layer runs the split
    local/remote schedule (``_gnn_layer_overlap``) instead of the blocking
    shuffle -> aggregate; ``spec.wire_dtype`` applies on either path.

    ``rep_block`` holds the statically replicated hot-vertex feature rows
    (DESIGN.md "Partitioning & replication"). It only applies to the input
    layer (li == L-1): plans built with a replication set address those
    sources past the recv region, so the block is appended to the mixed
    buffer after the (smaller) shuffle. Interior layers never see it.
    """
    h = h_input
    L = spec.num_layers
    for li in range(L - 1, -1, -1):
        lp = plan_arrays["layers"][li]
        num_out = lp["self_pos"].shape[-1]  # static: N_i
        layer_params = params[L - 1 - li]  # params[0] consumes input features
        rep = rep_block if li == L - 1 else None
        with jax.named_scope(f"gnn/layer{L - 1 - li}"):
            if spec.overlap:
                h = _gnn_layer_overlap(
                    spec, layer_params, h, lp, num_out, li == 0, SimComm(),
                    rep_block=rep,
                )
                continue
            with jax.named_scope("shuffle"):
                mixed = shuffle_fn(h, lp["send_idx"], spec.wire_dtype)  # (P, M, F)
            if rep is not None:
                mixed = sim_append_replicated(mixed, rep)
            lp_dev = {k: v for k, v in lp.items() if k != "send_idx"}
            apply_one = lambda m, l: gnn_layer_apply(  # noqa: E731
                spec, layer_params, m, l, num_out, is_last=(li == 0)
            )
            h = jax.vmap(apply_one)(mixed, lp_dev)
    return h


def gnn_forward_cached(
    spec: GNNSpec,
    params: list[dict],
    cache_block: jnp.ndarray,  # (P, C, F) device-resident feature cache
    miss_feats: jnp.ndarray,  # (P, M, F) host-gathered cache-miss rows
    plan_arrays: dict,  # plan pytree incl. the "cache" serving recipe
    shuffle_fn,
    rep_block: jnp.ndarray | None = None,  # (R, F_in) replicated input rows
) -> jnp.ndarray:
    """Split-parallel forward with the loading stage folded into the step.

    Instead of consuming a pre-gathered (P, N_L, F) block, the input
    features are assembled on device from the resident cache block plus the
    compacted miss rows (``core.shuffle.sim_serve_features``) — numerically
    identical to ``gnn_forward(load_features(...))`` but the host link only
    carried the misses.
    """
    from repro.core.shuffle import sim_serve_features

    h_input = sim_serve_features(
        cache_block, plan_arrays["cache"], miss_feats,
        wire_dtype=spec.wire_dtype,
    )
    return gnn_forward(
        spec, params, h_input, plan_arrays, shuffle_fn, rep_block=rep_block
    )


def gnn_forward_spmd(
    spec: GNNSpec,
    params: list[dict],
    h_input: jnp.ndarray,  # (N_L, F) input rows — or (M, F) misses if cached
    plan_arrays: dict,  # per-device slices (leading P axis removed)
    axis_name: str,
    cache_local: jnp.ndarray | None = None,  # (C, F) resident cache shard
    rep_block: jnp.ndarray | None = None,  # (R, F_in) replicated input rows
) -> jnp.ndarray:
    """Per-device forward for `shard_map` execution (same math as sim mode).

    When ``cache_local`` is given, ``h_input`` is the (M, F) miss block and
    the input rows are served from the sharded resident cache first
    (``spmd_serve_features`` — the mirror of ``gnn_forward_cached``).
    ``rep_block`` is the fully replicated hot-vertex block (identical on
    every device); it is appended after the input-layer shuffle exactly as
    in ``gnn_forward``.
    """
    from repro.core.shuffle import spmd_serve_features, spmd_shuffle

    if cache_local is not None:
        h_input = spmd_serve_features(
            cache_local, plan_arrays["cache"], h_input, axis_name,
            wire_dtype=spec.wire_dtype,
        )
    h = h_input
    L = spec.num_layers
    for li in range(L - 1, -1, -1):
        lp = plan_arrays["layers"][li]
        num_out = lp["self_pos"].shape[-1]
        rep = rep_block if li == L - 1 else None
        with jax.named_scope(f"gnn/layer{L - 1 - li}"):
            if spec.overlap:
                h = _gnn_layer_overlap(
                    spec, params[L - 1 - li], h, lp, num_out, li == 0,
                    SpmdComm(axis_name), rep_block=rep,
                )
                continue
            with jax.named_scope("shuffle"):
                mixed = spmd_shuffle(
                    h, lp["send_idx"], axis_name, spec.wire_dtype
                )
            if rep is not None:
                mixed = spmd_append_replicated(mixed, rep)
            h = gnn_layer_apply(
                spec,
                params[L - 1 - li],
                mixed,
                lp,
                num_out,
                is_last=(li == 0),
            )
    return h


def gnn_forward_split_mesh(
    spec: GNNSpec,
    mesh,  # launch.sharding.make_split_mesh(R, P): the split axis is minor
    params: list[dict],
    h_input: jnp.ndarray,  # (P, N_L, F) — or (P, M, F) misses if cached
    plan_arrays: dict,  # plan pytree with the leading P axis (sim layout)
    cache_block: jnp.ndarray | None = None,  # (P, C, F) resident cache
    rep_block: jnp.ndarray | None = None,  # (R, F_in) replicated input rows
) -> jnp.ndarray:
    """``gnn_forward_spmd`` under ``jax.shard_map`` on the mesh's split axis.

    Takes the sim-mode operands of ``gnn_forward``/``gnn_forward_cached``
    and returns the same (P, N_0, out_dim) logits: the leading P axis of
    the feature block, the plan and the cache block is sharded over the
    split axis, so each device runs its own slice and the shuffles are real
    all-to-alls. ``params`` and ``rep_block`` are replicated. Differentiable
    with respect to ``params`` and ``h_input``.
    """
    axis = mesh.axis_names[-1]
    split = PartitionSpec(axis)

    def body(params, h, pa, cache, rep):
        local = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)  # noqa: E731
        out = gnn_forward_spmd(
            spec, params, h[0], local(pa), axis,
            cache_local=None if cache is None else cache[0], rep_block=rep,
        )
        return out[None]

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec(), split, split, split, PartitionSpec()),
        out_specs=split, check_vma=False,
    )(params, h_input, plan_arrays, cache_block, rep_block)
