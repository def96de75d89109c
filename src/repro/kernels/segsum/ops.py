"""Jit'd wrapper for the segment-sum kernel: packing + padding + unpadding.

``pack_edges`` is the host-side packing used by the split plan (static shapes
per plan); ``segment_sum_pallas`` is the drop-in replacement for the jnp path
when a concrete (host) ``dst`` is available.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.kernels.segsum.kernel import segment_sum_packed


def _pow2_at_least(x: int, floor: int) -> int:
    p = floor
    while p < x:
        p <<= 1
    return p


def pack_edges(
    dst: np.ndarray,  # (E,) int32
    mask: np.ndarray,  # (E,) bool
    num_out: int,
    rows: int = 128,
    edge_block_floor: int = 128,
) -> dict:
    """Host-side packing: edges grouped by dst row-block, padded to EB slots.

    Returns perm (DB*EB,) indices into the edge axis (E = sentinel for
    padding -> callers append one zero row), local_dst (DB*EB, 1) with R as
    the padding sentinel, and the static dims.
    """
    E = dst.shape[0]
    DB = max((num_out + rows - 1) // rows, 1)
    valid = np.flatnonzero(mask)
    block_of = dst[valid] // rows
    order = np.argsort(block_of, kind="stable")
    valid = valid[order]
    block_of = block_of[order]
    counts = np.bincount(block_of, minlength=DB)
    EB = _pow2_at_least(int(counts.max(initial=1)), edge_block_floor)

    perm = np.full(DB * EB, E, dtype=np.int32)  # E = gather-a-zero-row sentinel
    local = np.full(DB * EB, rows, dtype=np.int32)  # rows = one-hot kill sentinel
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.arange(valid.shape[0]) - np.repeat(starts, counts)
    pos = block_of * EB + slot
    perm[pos] = valid
    local[pos] = dst[valid] - block_of * rows
    return {
        "perm": perm,
        "local_dst": local.reshape(-1, 1),
        "rows": rows,
        "edge_block": EB,
        "num_blocks": DB,
    }


def segment_sum_pallas(
    contrib: jnp.ndarray,  # (E, F)
    dst,  # (E,) — must be concrete (host) for packing
    mask,  # (E,) — must be concrete
    num_out: int,
    rows: int = 128,
    feat_block: int = 128,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Masked ``segment_sum(contrib, dst)`` -> (num_out, F) via the packed
    Pallas kernel.

    Contract (docs/KERNELS.md): ``dst`` in [0, num_out) for every slot,
    ``mask`` marks valid slots; masked slots contribute exactly 0 and empty
    segments are exact zeros. ``dst``/``mask`` must be *concrete* (the pack
    runs host-side), so this op cannot appear inside jit — the training step
    uses ``kernels.gather_segsum``, whose layout rides in the plan instead.
    Output dtype == ``contrib.dtype`` (accumulation is f32).
    """
    pack = pack_edges(np.asarray(dst), np.asarray(mask), num_out, rows=rows)
    return segment_sum_from_pack(
        contrib, pack, num_out, feat_block=feat_block, interpret=interpret
    )


def segment_sum_from_pack(
    contrib: jnp.ndarray,
    pack: dict,
    num_out: int,
    feat_block: int = 128,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Device-side: gather into packed order, run the kernel, unpad."""
    E, F = contrib.shape
    Fp = ((F + feat_block - 1) // feat_block) * feat_block
    contrib_z = jnp.concatenate(
        [contrib, jnp.zeros((1, F), contrib.dtype)], axis=0
    )  # sentinel row E
    packed = contrib_z[jnp.asarray(pack["perm"])]  # (DB*EB, F)
    if Fp != F:
        packed = jnp.pad(packed, ((0, 0), (0, Fp - F)))
    out = segment_sum_packed(
        packed,
        jnp.asarray(pack["local_dst"]),
        rows=pack["rows"],
        edge_block=pack["edge_block"],
        feat_block=feat_block,
        interpret=interpret,
    )  # (DB*rows, Fp)
    return out[:num_out, :F]
