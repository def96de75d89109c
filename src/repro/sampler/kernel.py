"""Pallas wavefront-expansion kernel (docs/SAMPLER.md §3).

One grid step expands a block of ``RB`` frontier vertices: for each (vertex,
slot) pair it hashes the counter-based key to a uniform draw and emits a
slot code (within-row neighbor offset / self-loop / invalid — see
``ref.expand_codes``, which the kernel body calls on its VMEM block so the
compiled kernel and the jnp backend are bit-identical).

Layout notes:

  * ``vid``/``deg`` ride as (B, 1) int32 columns (the repo's packed-index
    idiom, cf. ``gather_segsum``); the folded 64-bit layer key is a (1, 2)
    uint32 array — a *traced* input, so a new (epoch, batch) never
    recompiles.
  * The (RB, fanout) output block keeps the raw fanout as its lane
    dimension; real fanouts (4..16) are far below the 128 lane tile, which
    Mosaic masks. The expansion is VPU-only (integer hash + selects) — the
    kernel's value is keeping the whole wavefront in VMEM next to the
    dedup/exchange steps, not MXU math.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.sampler.ref import expand_codes

ROW_BLOCK = 128  # RB: frontier vertices expanded per grid step


def _expand_body(key_ref, vid_ref, deg_ref, out_ref, *, fanout):
    out_ref[...] = expand_codes(
        vid_ref[:, 0], deg_ref[:, 0], key_ref[0, 0], key_ref[0, 1], fanout
    )


@functools.partial(
    jax.jit, static_argnames=("fanout", "row_block", "interpret")
)
def wavefront_expand_kernel(
    vid: jnp.ndarray,  # (B,) int32, B a multiple of row_block
    deg: jnp.ndarray,  # (B,) int32; < 0 marks invalid rows
    key: jnp.ndarray,  # (1, 2) uint32 folded 64-bit layer key
    *,
    fanout: int,
    row_block: int = ROW_BLOCK,
    interpret: bool,
) -> jnp.ndarray:
    """Slot codes (B, fanout) int32 — the Pallas realization of the oracle."""
    B = vid.shape[0]
    assert B % row_block == 0, "caller pads B to the row block"
    grid = (B // row_block,)
    return pl.pallas_call(
        functools.partial(_expand_body, fanout=fanout),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
            pl.BlockSpec((row_block, 1), lambda i: (i, 0)),
            pl.BlockSpec((row_block, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((row_block, fanout), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, fanout), jnp.int32),
        interpret=interpret,
    )(key, vid[:, None], deg[:, None])
