"""Pallas TPU kernel: per-destination edge softmax (GAT normalization).

Same dst-row-block packed layout as the segsum kernel. Per grid step the
kernel holds an (EB, H) logit tile + (EB, 1) local-dst tile in VMEM and
computes, entirely on-chip:

  seg-max  via a broadcast-compare masked max  (VPU, (EB x R x Hb) masked)
  gather   of per-row max/denominator back to edges via one-hot MXU matmuls
  alpha    = exp(logit - max[dst]) / denom[dst]

CUDA GAT kernels do this with a two-pass atomic max/sum through shared
memory; the TPU formulation trades atomics for two small matmuls against the
same one-hot the aggregation kernel uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _edge_softmax_body(dst_ref, logits_ref, out_ref, *, rows: int):
    local_dst = dst_ref[:, 0]  # (EB,)
    logits = logits_ref[...].astype(jnp.float32)  # (EB, Hb)
    onehot = (
        local_dst[:, None] == jax.lax.iota(jnp.int32, rows)[None, :]
    ).astype(jnp.float32)  # (EB, R); padding rows all-zero

    # segment max: mask logits into (EB, R, Hb) and reduce the edge axis
    neg = jnp.float32(-1e30)
    expanded = jnp.where(
        onehot[:, :, None] > 0, logits[:, None, :], neg
    )  # (EB, R, Hb)
    seg_max = jnp.max(expanded, axis=0)  # (R, Hb)
    seg_max = jnp.maximum(seg_max, neg)

    # gather per-edge max via one-hot matmul; padding edges get 0
    edge_max = jax.lax.dot_general(
        onehot, seg_max, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (EB, Hb)
    valid = (local_dst < rows)[:, None].astype(jnp.float32)
    ex = jnp.exp(logits - edge_max) * valid  # (EB, Hb)

    denom = jax.lax.dot_general(
        onehot, ex, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (R, Hb)
    edge_denom = jax.lax.dot_general(
        onehot, denom, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (EB, Hb)
    out_ref[...] = (ex / jnp.maximum(edge_denom, 1e-30)).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("rows", "edge_block", "head_block", "interpret")
)
def edge_softmax_packed(
    logits_packed: jnp.ndarray,  # (DB*EB, H)
    local_dst: jnp.ndarray,  # (DB*EB, 1) int32, R = padding sentinel
    *,
    rows: int = 128,
    edge_block: int = 512,
    head_block: int = 8,
    interpret: bool,
) -> jnp.ndarray:
    total, H = logits_packed.shape
    EB = edge_block
    assert total % EB == 0
    DB = total // EB
    assert H % head_block == 0

    return pl.pallas_call(
        functools.partial(_edge_softmax_body, rows=rows),
        grid=(DB, H // head_block),
        in_specs=[
            pl.BlockSpec((EB, 1), lambda db, hb: (db, 0)),
            pl.BlockSpec((EB, head_block), lambda db, hb: (db, hb)),
        ],
        out_specs=pl.BlockSpec((EB, head_block), lambda db, hb: (db, hb)),
        out_shape=jax.ShapeDtypeStruct((DB * EB, H), logits_packed.dtype),
        interpret=interpret,
    )(local_dst, logits_packed)
