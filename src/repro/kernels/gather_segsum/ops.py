"""Jit'd entry points for the fused gather->segment-aggregate kernels.

All ops consume the *plan-carried* dst-sorted layout (``layout.py``,
docs/KERNELS.md): ``pack_perm``/``pack_dst`` are (DB, EB) device arrays built
once on the plan producer thread, so — unlike the legacy ``segsum`` wrapper,
which packs host-side and needs concrete indices — these ops are fully
traceable: they run inside jit/vmap/shard_map and are differentiable via
custom VJPs that call the adjoint kernels in ``kernel.py`` (jax cannot
autodiff through a ``pallas_call``; the adjoints reuse the same layout with
gather/scatter roles swapped).

Contract (shared by all ops):
  mixed      (M, F) float   — mixed-frontier rows; padding rows' values are
                              irrelevant (never addressed by valid edges).
  edge_src   (E,)   int32   — per-edge source row into ``mixed``; entries of
                              masked edges are arbitrary (killed by layout).
  pack_perm  (DB, EB) int32 — slot -> edge index; padding slots arbitrary.
  pack_dst   (DB, EB) int32 — slot -> dst - db*R; **R marks padding slots**.
  num_out    static int     — destination rows; output is (num_out, F).

Accumulation is f32 (f64 for f64 inputs), cast back to ``mixed.dtype``. The
sums visit edges in packed order, so results match the jnp oracle to fp
tolerance, not bit-for-bit (see docs/KERNELS.md for the tested bounds).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gather_segsum.layout import AGG_ROWS
from repro.kernels.gather_segsum.kernel import (
    gather_segsum_bwd_mixed,
    gather_segsum_bwd_w,
    gather_segsum_fwd,
)


def _roundup(x: int, m: int) -> int:
    return max(((x + m - 1) // m) * m, m)


def _acc_dtype(dtype):
    return jnp.float64 if dtype == jnp.float64 else jnp.float32


def _pack_src(edge_src, pack_perm, pack_dst, rows, sentinel):
    """Per-slot source row, derived in-jit so repad rebasing of ``edge_src``
    (DESIGN.md §3) propagates automatically. Padding slots -> ``sentinel``
    (>= padded M), which no kernel tile ever matches."""
    E = edge_src.shape[0]
    flat_perm = pack_perm.reshape(-1)
    flat_dst = pack_dst.reshape(-1)
    src = edge_src.astype(jnp.int32)[jnp.clip(flat_perm, 0, E - 1)]
    return jnp.where(flat_dst < rows, src, jnp.int32(sentinel))[:, None]


# --------------------------------------------------------------------------- #
# unweighted sum: custom VJP around the forward/adjoint kernel pair
# --------------------------------------------------------------------------- #
# statics lead the signature: custom_vjp's nondiff_argnums must name leading
# arguments in the pinned jax, or they arrive in bwd as tracers
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5, 6))
def _fused_sum(mem_rows, rows, edge_block, mem_block, feat_block, acc_dtype,
               interpret, mixed_p, pack_src, pack_dst):
    return gather_segsum_fwd(
        mixed_p, pack_src, pack_dst, None,
        rows=rows, edge_block=edge_block, mem_block=mem_block,
        feat_block=feat_block, acc_dtype=acc_dtype, interpret=interpret,
    )


def _fused_sum_fwd(mem_rows, rows, edge_block, mem_block, feat_block,
                   acc_dtype, interpret, mixed_p, pack_src, pack_dst):
    out = _fused_sum(
        mem_rows, rows, edge_block, mem_block, feat_block, acc_dtype,
        interpret, mixed_p, pack_src, pack_dst,
    )
    return out, (pack_src, pack_dst)


def _fused_sum_bwd(mem_rows, rows, edge_block, mem_block, feat_block,
                   acc_dtype, interpret, res, g):
    pack_src, pack_dst = res
    gm = gather_segsum_bwd_mixed(
        g, pack_src, pack_dst, None,
        mem_rows=mem_rows, rows=rows, edge_block=edge_block,
        mem_block=mem_block, feat_block=feat_block, acc_dtype=acc_dtype,
        interpret=interpret,
    )
    return gm, None, None


_fused_sum.defvjp(_fused_sum_fwd, _fused_sum_bwd)


# --------------------------------------------------------------------------- #
# weighted sum (GAT): cotangents for both the rows and the per-slot weights
# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5, 6))
def _fused_weighted(rows, edge_block, mem_block, feat_block, head_dim,
                    acc_dtype, interpret, mixed_p, w_packed, pack_src,
                    pack_dst):
    return gather_segsum_fwd(
        mixed_p, pack_src, pack_dst, w_packed,
        rows=rows, edge_block=edge_block, mem_block=mem_block,
        feat_block=feat_block, head_dim=head_dim, acc_dtype=acc_dtype,
        interpret=interpret,
    )


def _fused_weighted_fwd(rows, edge_block, mem_block, feat_block, head_dim,
                        acc_dtype, interpret, mixed_p, w_packed, pack_src,
                        pack_dst):
    out = _fused_weighted(
        rows, edge_block, mem_block, feat_block, head_dim, acc_dtype,
        interpret, mixed_p, w_packed, pack_src, pack_dst,
    )
    return out, (mixed_p, w_packed, pack_src, pack_dst)


def _fused_weighted_bwd(rows, edge_block, mem_block, feat_block, head_dim,
                        acc_dtype, interpret, res, g):
    mixed_p, w_packed, pack_src, pack_dst = res
    gm = gather_segsum_bwd_mixed(
        g, pack_src, pack_dst, w_packed,
        mem_rows=mixed_p.shape[0], rows=rows, edge_block=edge_block,
        mem_block=mem_block, feat_block=feat_block, head_dim=head_dim,
        acc_dtype=acc_dtype, interpret=interpret,
    )
    gw = gather_segsum_bwd_w(
        mixed_p, g, pack_src, pack_dst,
        num_heads=w_packed.shape[1], rows=rows, edge_block=edge_block,
        mem_block=mem_block, feat_block=feat_block, head_dim=head_dim,
        acc_dtype=acc_dtype, interpret=interpret,
    )
    return gm, gw, None, None


_fused_weighted.defvjp(_fused_weighted_fwd, _fused_weighted_bwd)


# --------------------------------------------------------------------------- #
# public ops
# --------------------------------------------------------------------------- #
def gather_segment_sum(
    mixed: jnp.ndarray,  # (M, F)
    edge_src: jnp.ndarray,  # (E,) int32
    pack_perm: jnp.ndarray,  # (DB, EB) int32
    pack_dst: jnp.ndarray,  # (DB, EB) int32
    num_out: int,
    *,
    rows: int = AGG_ROWS,
    mem_block: int = 128,
    feat_block: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    """Fused ``segment_sum(mixed[edge_src], dst)`` -> (num_out, F).

    Never materializes the (E, F) per-edge buffer; masked edges (padding
    slots, ``pack_dst == rows``) contribute exactly 0. Differentiable w.r.t.
    ``mixed``; usable under jit/vmap/shard_map (indices are device arrays).
    """
    M, F = mixed.shape
    DB, EB = pack_perm.shape
    Mp, Fp = _roundup(M, mem_block), _roundup(F, feat_block)
    acc = _acc_dtype(mixed.dtype)
    # cast at the custom-vjp boundary so primal and cotangent dtypes agree
    # (accumulation runs in ``acc`` regardless of the storage dtype)
    mixed_p = jnp.pad(mixed, ((0, Mp - M), (0, Fp - F))).astype(acc)
    pack_src = _pack_src(edge_src, pack_perm, pack_dst, rows, Mp)
    out = _fused_sum(
        Mp, rows, EB, mem_block, feat_block, acc, interpret,
        mixed_p, pack_src, pack_dst.reshape(-1, 1),
    )
    return out[:num_out, :F].astype(mixed.dtype)


def gather_segment_mean(
    mixed: jnp.ndarray,
    edge_src: jnp.ndarray,
    pack_perm: jnp.ndarray,
    pack_dst: jnp.ndarray,
    seg_offsets: jnp.ndarray,  # (num_out + 1,) int32 CSR offsets
    num_out: int,
    *,
    rows: int = AGG_ROWS,
    mem_block: int = 128,
    feat_block: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    """Fused masked segment mean -> (num_out, F).

    The denominator comes from the plan's CSR offsets (exact integer counts,
    no device-side mask reduction); destinations with zero valid edges
    return exact zeros.
    """
    total = gather_segment_sum(
        mixed, edge_src, pack_perm, pack_dst, num_out,
        rows=rows, mem_block=mem_block, feat_block=feat_block,
        interpret=interpret,
    )
    count = (seg_offsets[1:] - seg_offsets[:-1]).astype(total.dtype)
    return total / jnp.maximum(count, 1.0)[:, None]


def gather_weighted_segsum(
    mixed: jnp.ndarray,  # (M, F) with F = H * dh, head-major columns
    weights: jnp.ndarray,  # (E, H) per-edge per-head weights (GAT alpha)
    edge_src: jnp.ndarray,
    pack_perm: jnp.ndarray,
    pack_dst: jnp.ndarray,
    num_out: int,
    *,
    rows: int = AGG_ROWS,
    mem_block: int = 128,
    feat_block: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    """Fused ``segment_sum(weights[e, h] * mixed[src, h*dh:(h+1)*dh], dst)``.

    The softmax-weighted aggregation of GAT. Differentiable w.r.t. both
    ``mixed`` and ``weights`` (the weight cotangent routes back through the
    in-jit pack gather below, so upstream softmax logits train normally).
    ``F % H == 0`` is required; no alignment between ``feat_block`` and the
    head width is needed — the in-kernel head map is exact per column.
    """
    M, F = mixed.shape
    E, H = weights.shape
    assert F % H == 0, "weighted segsum: feature dim must split across heads"
    dh = F // H
    DB, EB = pack_perm.shape
    Mp, Fp = _roundup(M, mem_block), _roundup(F, feat_block)
    acc = _acc_dtype(mixed.dtype)
    mixed_p = jnp.pad(mixed, ((0, Mp - M), (0, Fp - F))).astype(acc)
    flat_perm = pack_perm.reshape(-1)
    flat_dst = pack_dst.reshape(-1)
    pack_src = _pack_src(edge_src, pack_perm, pack_dst, rows, Mp)
    # pack the weights in-jit (E*H traffic — tiny next to E*F); padding
    # slots get exact zeros so column padding beyond F stays inert
    w_packed = weights.astype(acc)[jnp.clip(flat_perm, 0, E - 1)]
    w_packed = w_packed * (flat_dst < rows)[:, None].astype(acc)
    out = _fused_weighted(
        rows, EB, mem_block, feat_block, dh, acc, interpret,
        mixed_p, w_packed, pack_src, flat_dst[:, None],
    )
    return out[:num_out, :F].astype(mixed.dtype)
