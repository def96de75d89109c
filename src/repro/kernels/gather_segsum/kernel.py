"""Pallas TPU kernels: fused gather -> segment-aggregate over dst-row tiles.

The unfused hot path materializes ``mixed[edge_src]`` — an (E, F) buffer in
HBM — and reduces it with a scatter-add. Here both happen in one pass: per
grid step the kernel holds an (EB,) packed edge tile and an (MB, FB) slice of
the mixed-frontier buffer in VMEM, gathers the edge's source rows with a
one-hot MXU matmul, and accumulates them into an (R, FB) destination tile
with a second one-hot matmul. Per-edge feature rows never touch HBM.

  mixed      -- (Mp, Fp) mixed-frontier rows (padded to MB / FB multiples)
  pack_src   -- (DB*EB, 1) int32 source row per packed slot; sentinel >= Mp
  pack_dst   -- (DB*EB, 1) int32 local dst (dst - db*R) in [0, R); sentinel R
  weights    -- (DB*EB, H) optional per-slot per-head weights (GAT alpha)

Forward (grid fb, db, mb — mb innermost accumulates over source tiles):

  out[db*R + r, fb] += onehot_dst.T @ ((onehot_src @ mixed_tile) * w_tile)

The redundancy-vs-bandwidth trade: the fused pass re-reads the mixed buffer
once per destination block (DB * M * F bytes) instead of streaming 3 * E * F
bytes of per-edge buffer — a win whenever the average in-tile degree
E / (DB * M) beats 1/3 (high fan-out), measured by benchmarks/kernel_bench.

Backward is NOT jax autodiff (``pl.program_id`` has no JVP rule; the
accumulation transpose would be wrong anyway): ``ops.py`` wires custom VJPs
to the two adjoint kernels below, which reuse the same packed layout with
gather/scatter roles swapped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gather_segsum.layout import AGG_ROWS


def _onehot(idx, width, dtype):
    """(len(idx), width) one-hot; out-of-range entries give all-zero rows."""
    return (
        idx[:, None] == jax.lax.iota(jnp.int32, width)[None, :]
    ).astype(dtype)


def _head_onehot(fb, feat_block, num_heads, head_dim, dtype):
    """(H, FB) map of feature columns to heads: col j -> head (fb*FB+j)//dh."""
    col_head = (
        jax.lax.broadcasted_iota(jnp.int32, (num_heads, feat_block), 1)
        + fb * feat_block
    ) // head_dim
    head_row = jax.lax.broadcasted_iota(
        jnp.int32, (num_heads, feat_block), 0
    )
    return (head_row == col_head).astype(dtype)


def _dot(a, b, contract, acc_dtype):
    return jax.lax.dot_general(
        a, b, ((contract, (0,)), ((), ())), preferred_element_type=acc_dtype
    )


def _fwd_body(
    *refs, rows, mem_block, feat_block, head_dim, weighted, acc_dtype
):
    if weighted:
        src_ref, dst_ref, w_ref, mixed_ref, out_ref = refs
    else:
        src_ref, dst_ref, mixed_ref, out_ref = refs
    fb, mb = pl.program_id(0), pl.program_id(2)
    local_src = src_ref[:, 0] - mb * mem_block  # (EB,)
    gathered = _dot(
        _onehot(local_src, mem_block, acc_dtype),
        mixed_ref[...].astype(acc_dtype),
        (1,),
        acc_dtype,
    )  # (EB, FB)
    if weighted:
        w_tile = _dot(
            w_ref[...].astype(acc_dtype),
            _head_onehot(fb, feat_block, w_ref.shape[1], head_dim, acc_dtype),
            (1,),
            acc_dtype,
        )  # (EB, FB)
        gathered = gathered * w_tile
    part = _dot(
        _onehot(dst_ref[:, 0], rows, acc_dtype), gathered, (0,), acc_dtype
    )  # (R, FB); sentinel slots (dst == R) contribute nothing

    @pl.when(mb == 0)
    def _init():
        out_ref[...] = part

    @pl.when(mb > 0)
    def _acc():
        out_ref[...] += part


def _bwd_mixed_body(
    *refs, rows, mem_block, feat_block, head_dim, weighted, acc_dtype
):
    if weighted:
        src_ref, dst_ref, w_ref, g_ref, out_ref = refs
    else:
        src_ref, dst_ref, g_ref, out_ref = refs
    fb, mb, db = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    ge = _dot(
        _onehot(dst_ref[:, 0], rows, acc_dtype),
        g_ref[...].astype(acc_dtype),
        (1,),
        acc_dtype,
    )  # (EB, FB) = cotangent of each packed edge's destination row
    if weighted:
        w_tile = _dot(
            w_ref[...].astype(acc_dtype),
            _head_onehot(fb, feat_block, w_ref.shape[1], head_dim, acc_dtype),
            (1,),
            acc_dtype,
        )
        ge = ge * w_tile
    local_src = src_ref[:, 0] - mb * mem_block
    part = _dot(
        _onehot(local_src, mem_block, acc_dtype), ge, (0,), acc_dtype
    )  # (MB, FB): scatter-add by source row via the transposed one-hot

    @pl.when(db == 0)
    def _init():
        out_ref[...] = part

    @pl.when(db > 0)
    def _acc():
        out_ref[...] += part


def _bwd_w_body(
    src_ref, dst_ref, mixed_ref, g_ref, out_ref,
    *, rows, mem_block, feat_block, head_dim, acc_dtype,
):
    fb, mb = pl.program_id(1), pl.program_id(2)
    local_src = src_ref[:, 0] - mb * mem_block
    gm = _dot(
        _onehot(local_src, mem_block, acc_dtype),
        mixed_ref[...].astype(acc_dtype),
        (1,),
        acc_dtype,
    )  # (EB, FB) gathered source rows
    ge = _dot(
        _onehot(dst_ref[:, 0], rows, acc_dtype),
        g_ref[...].astype(acc_dtype),
        (1,),
        acc_dtype,
    )  # (EB, FB) gathered output cotangents
    part = _dot(
        gm * ge,
        _head_onehot(
            fb, feat_block, out_ref.shape[1], head_dim, acc_dtype
        ).T,
        (1,),
        acc_dtype,
    )  # (EB, H): dL/dw summed over this (fb, mb) tile's columns

    @pl.when(jnp.logical_and(fb == 0, mb == 0))
    def _init():
        out_ref[...] = part

    @pl.when(jnp.logical_or(fb > 0, mb > 0))
    def _acc():
        out_ref[...] += part


def _pack_specs(edge_block, num_heads, weighted, index_map):
    specs = [
        pl.BlockSpec((edge_block, 1), index_map),
        pl.BlockSpec((edge_block, 1), index_map),
    ]
    if weighted:
        specs.append(pl.BlockSpec((edge_block, num_heads), index_map))
    return specs


@functools.partial(
    jax.jit,
    static_argnames=(
        "rows", "edge_block", "mem_block", "feat_block", "head_dim",
        "acc_dtype", "interpret",
    ),
)
def gather_segsum_fwd(
    mixed: jnp.ndarray,  # (Mp, Fp)
    pack_src: jnp.ndarray,  # (DB*EB, 1) int32
    pack_dst: jnp.ndarray,  # (DB*EB, 1) int32
    weights: jnp.ndarray | None = None,  # (DB*EB, H) or None
    *,
    rows: int = AGG_ROWS,
    edge_block: int,
    mem_block: int = 128,
    feat_block: int = 128,
    head_dim: int = 0,  # dh (weighted only)
    acc_dtype=jnp.float32,
    interpret: bool,
) -> jnp.ndarray:
    """Fused forward: (DB*R, Fp) per-destination sums in ``acc_dtype``."""
    Mp, Fp = mixed.shape
    EB = edge_block
    DB = pack_src.shape[0] // EB
    weighted = weights is not None
    grid = (Fp // feat_block, DB, Mp // mem_block)
    body = functools.partial(
        _fwd_body,
        rows=rows, mem_block=mem_block, feat_block=feat_block,
        head_dim=head_dim, weighted=weighted, acc_dtype=acc_dtype,
    )
    in_specs = _pack_specs(
        EB, weights.shape[1] if weighted else 0, weighted,
        lambda fb, db, mb: (db, 0),
    )
    in_specs.append(
        pl.BlockSpec((mem_block, feat_block), lambda fb, db, mb: (mb, fb))
    )
    args = [pack_src, pack_dst] + ([weights] if weighted else []) + [mixed]
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, feat_block), lambda fb, db, mb: (db, fb)),
        out_shape=jax.ShapeDtypeStruct((DB * rows, Fp), acc_dtype),
        interpret=interpret,
    )(*args)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mem_rows", "rows", "edge_block", "mem_block", "feat_block",
        "head_dim", "acc_dtype", "interpret",
    ),
)
def gather_segsum_bwd_mixed(
    g: jnp.ndarray,  # (DB*R, Fp) output cotangent
    pack_src: jnp.ndarray,
    pack_dst: jnp.ndarray,
    weights: jnp.ndarray | None = None,
    *,
    mem_rows: int,  # Mp (padded mixed height)
    rows: int = AGG_ROWS,
    edge_block: int,
    mem_block: int = 128,
    feat_block: int = 128,
    head_dim: int = 0,
    acc_dtype=jnp.float32,
    interpret: bool,
) -> jnp.ndarray:
    """Adjoint w.r.t. ``mixed``: (Mp, Fp) — same layout, roles swapped."""
    _, Fp = g.shape
    EB = edge_block
    DB = pack_src.shape[0] // EB
    weighted = weights is not None
    grid = (Fp // feat_block, mem_rows // mem_block, DB)
    body = functools.partial(
        _bwd_mixed_body,
        rows=rows, mem_block=mem_block, feat_block=feat_block,
        head_dim=head_dim, weighted=weighted, acc_dtype=acc_dtype,
    )
    in_specs = _pack_specs(
        EB, weights.shape[1] if weighted else 0, weighted,
        lambda fb, mb, db: (db, 0),
    )
    in_specs.append(
        pl.BlockSpec((rows, feat_block), lambda fb, mb, db: (db, fb))
    )
    args = [pack_src, pack_dst] + ([weights] if weighted else []) + [g]
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (mem_block, feat_block), lambda fb, mb, db: (mb, fb)
        ),
        out_shape=jax.ShapeDtypeStruct((mem_rows, Fp), acc_dtype),
        interpret=interpret,
    )(*args)


@functools.partial(
    jax.jit,
    static_argnames=(
        "rows", "edge_block", "mem_block", "feat_block", "head_dim",
        "num_heads", "acc_dtype", "interpret",
    ),
)
def gather_segsum_bwd_w(
    mixed: jnp.ndarray,  # (Mp, Fp)
    g: jnp.ndarray,  # (DB*R, Fp)
    pack_src: jnp.ndarray,
    pack_dst: jnp.ndarray,
    *,
    num_heads: int,
    rows: int = AGG_ROWS,
    edge_block: int,
    mem_block: int = 128,
    feat_block: int = 128,
    head_dim: int,
    acc_dtype=jnp.float32,
    interpret: bool,
) -> jnp.ndarray:
    """Adjoint w.r.t. the per-slot weights: (DB*EB, H)."""
    Mp, Fp = mixed.shape
    EB = edge_block
    DB = pack_src.shape[0] // EB
    grid = (DB, Fp // feat_block, Mp // mem_block)
    body = functools.partial(
        _bwd_w_body,
        rows=rows, mem_block=mem_block, feat_block=feat_block,
        head_dim=head_dim, acc_dtype=acc_dtype,
    )
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((EB, 1), lambda db, fb, mb: (db, 0)),
            pl.BlockSpec((EB, 1), lambda db, fb, mb: (db, 0)),
            pl.BlockSpec((mem_block, feat_block), lambda db, fb, mb: (mb, fb)),
            pl.BlockSpec((rows, feat_block), lambda db, fb, mb: (db, fb)),
        ],
        out_specs=pl.BlockSpec((EB, num_heads), lambda db, fb, mb: (db, 0)),
        out_shape=jax.ShapeDtypeStruct((DB * EB, num_heads), acc_dtype),
        interpret=interpret,
    )(pack_src, pack_dst, mixed, g)
