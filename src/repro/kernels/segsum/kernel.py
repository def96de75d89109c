"""Pallas TPU kernel: segment-sum as a one-hot MXU matmul over dst-row blocks.

TPU adaptation of the GNN aggregation hot-spot (DESIGN.md §3). CUDA systems
map one warp per destination row and scatter-add through L2; on TPU,
scatter-add is serial but the MXU turns a segment reduction into a dense
``onehot.T @ contrib`` matmul. Edges are pre-packed (host-side, by the split
plan) so that block ``db`` holds only edges whose destination lies in rows
``[db*R, (db+1)*R)``:

  contrib_packed -- (DB*EB, F) edge messages (padding rows arbitrary)
  local_dst      -- (DB*EB, 1) int32, dst - db*R in [0, R); ``R`` = padding

Grid = (DB, F/FB). Each step loads an (EB, FB) message tile + (EB, 1) index
tile into VMEM, builds the (EB, R) one-hot, and emits an (R, FB) output tile:
one MXU matmul of shape (R x EB) @ (EB x FB). All tile dims are multiples of
128 for MXU/VREG alignment (EB, R, FB configurable).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _segsum_body(dst_ref, contrib_ref, out_ref, *, rows: int):
    local_dst = dst_ref[:, 0]  # (EB,)
    contrib = contrib_ref[...]  # (EB, FB)
    onehot = (
        local_dst[:, None] == jax.lax.iota(jnp.int32, rows)[None, :]
    ).astype(contrib.dtype)  # (EB, R); padding rows (dst==R) are all-zero
    out_ref[...] = jax.lax.dot_general(
        onehot,
        contrib,
        dimension_numbers=(((0,), (0,)), ((), ())),  # contract over EB
        preferred_element_type=jnp.float32,
    ).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("rows", "edge_block", "feat_block", "interpret")
)
def segment_sum_packed(
    contrib_packed: jnp.ndarray,  # (DB*EB, F)
    local_dst: jnp.ndarray,  # (DB*EB, 1) int32
    *,
    rows: int = 128,  # R: dst rows per block
    edge_block: int = 512,  # EB
    feat_block: int = 128,  # FB
    interpret: bool,
) -> jnp.ndarray:
    total, F = contrib_packed.shape
    EB = edge_block
    assert total % EB == 0, "contrib must be packed to a multiple of edge_block"
    DB = total // EB
    assert F % feat_block == 0, "feature dim must be padded to feat_block"

    return pl.pallas_call(
        functools.partial(_segsum_body, rows=rows),
        grid=(DB, F // feat_block),
        in_specs=[
            pl.BlockSpec((EB, 1), lambda db, fb: (db, 0)),
            pl.BlockSpec((EB, feat_block), lambda db, fb: (db, fb)),
        ],
        out_specs=pl.BlockSpec((rows, feat_block), lambda db, fb: (db, fb)),
        out_shape=jax.ShapeDtypeStruct((DB * rows, F), contrib_packed.dtype),
        interpret=interpret,
    )(local_dst, contrib_packed)
