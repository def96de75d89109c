"""Jit'd wrapper for the edge-softmax kernel (shares the segsum packing)."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.kernels.edge_softmax.kernel import edge_softmax_packed
from repro.kernels.segsum.ops import pack_edges


def edge_softmax_pallas(
    logits: jnp.ndarray,  # (E, H)
    dst,  # (E,) concrete
    mask,  # (E,) concrete
    num_out: int,
    rows: int = 128,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Per-destination softmax over incoming edges: (E, H) -> (E, H).

    Contract (docs/KERNELS.md): masked edges receive weight exactly 0 and
    are excluded from the normalization; destinations whose edges are all
    masked produce only zeros (never NaN — the kernel normalizes in f32
    with a finite max clamp). ``dst``/``mask`` must be concrete (host-side
    packing); valid edges of one destination sum to 1 within f32 rounding.
    """
    pack = pack_edges(np.asarray(dst), np.asarray(mask), num_out, rows=rows)
    return edge_softmax_from_pack(logits, pack, interpret=interpret)


def edge_softmax_from_pack(
    logits: jnp.ndarray,
    pack: dict,
    head_block: int = 8,
    *,
    interpret: bool,
) -> jnp.ndarray:
    E, H = logits.shape
    Hp = ((H + head_block - 1) // head_block) * head_block
    logits_z = jnp.concatenate([logits, jnp.zeros((1, H), logits.dtype)], axis=0)
    perm = jnp.asarray(pack["perm"])
    packed = logits_z[perm]
    if Hp != H:
        packed = jnp.pad(packed, ((0, 0), (0, Hp - H)))
    alpha_packed = edge_softmax_packed(
        packed,
        jnp.asarray(pack["local_dst"]),
        rows=pack["rows"],
        edge_block=pack["edge_block"],
        head_block=head_block,
        interpret=interpret,
    )[:, :H]
    # scatter back to edge order (sentinel slots land in the dummy row E)
    out = jnp.zeros((E + 1, H), logits.dtype)
    out = out.at[perm].set(alpha_packed)
    return out[:E]
