"""Pure-jnp oracle for the wavefront-expansion kernel.

``expand_codes`` is the *semantic definition* of one frontier expansion —
the Pallas kernel body (``kernel.py``) calls it on VMEM blocks, and the
``"jnp"`` backend calls it directly, so the two backends are bit-identical
by construction (same hash, same select logic).

Slot-code encoding (one int32 per (vertex, slot)):

  * ``>= 0`` -- a valid within-row neighbor offset: edge id is
    ``row_start + code``;
  * ``-1``   -- a self-loop (the vertex has zero in-degree — every vertex
    must have at least one message source, matching ``_sample_layer``);
  * ``-2``   -- an invalid slot (padding row, beyond-degree take-all slot,
    or a de-duplicated repeated draw).

Semantics mirror the host sampler exactly: ``deg <= fanout`` takes all
``deg`` in-edges; ``deg > fanout`` draws ``fanout`` uniform slots with
replacement then de-duplicates repeated draws of the same edge; ``deg == 0``
emits the self-loop. Rows are marked invalid by ``deg < 0``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sampler.rng import draw_u32

INVALID = -2
SELF_LOOP = -1


def expand_codes(
    vid: jnp.ndarray,  # (B,) int32 global vertex ids
    deg: jnp.ndarray,  # (B,) int32 in-degrees; < 0 marks an invalid row
    key_lo: jnp.ndarray,  # () uint32 — low lane of the 64-bit layer key
    key_hi: jnp.ndarray,  # () uint32 — high lane
    fanout: int,
) -> jnp.ndarray:
    """Slot codes (B, fanout) int32 for one frontier block (see module doc)."""
    B = vid.shape[0]
    slots = jax.lax.broadcasted_iota(jnp.int32, (B, fanout), 1)
    u = draw_u32(
        vid.astype(jnp.uint32)[:, None], slots.astype(jnp.uint32),
        key_lo, key_hi,
    )
    # The body is written for Mosaic as well as XLA: booleans come only
    # from comparisons and combine only with &/|/~ (Mosaic cannot select
    # between, broadcast or reduce bool vectors), and the unsigned modulo is
    # ``lax.rem`` (``%`` adds a signed-remainder fix-up built from bool
    # selects; for uint32 both give the same value).
    degc = jnp.maximum(deg, 1)
    sampled = jax.lax.rem(u, degc.astype(jnp.uint32)[:, None])
    take_all = (deg <= fanout)[:, None]
    off = jnp.where(take_all, slots, sampled.astype(jnp.int32))
    # deg < 0: nothing; deg == 0: slot 0 (the self-loop); deg <= fanout:
    # the first deg slots; deg > fanout: every slot
    valid = (deg >= 0)[:, None] & (
        (slots < degc[:, None]) | (deg > fanout)[:, None]
    )
    off = jnp.where((deg == 0)[:, None] & (slots == 0), SELF_LOOP, off)
    # de-duplicate repeated draws of the same edge: slot j dies if any k < j
    # drew the same offset (take-all offsets are distinct, so only sampled
    # rows are affected). fanout is small and static, so the unrolled
    # column compares are cheap and avoid data-dependent control flow.
    dup = slots < 0
    for k in range(fanout - 1):
        dup = dup | ((off == off[:, k:k + 1]) & (slots > k))
    valid = valid & ~dup
    return jnp.where(valid, off, INVALID)


def wavefront_expand_ref(
    vid: jnp.ndarray, deg: jnp.ndarray, key: jnp.ndarray, fanout: int
) -> jnp.ndarray:
    """The jnp backend: ``expand_codes`` on the whole block; ``key`` (2,)."""
    return expand_codes(vid, deg, key[0], key[1], fanout)
