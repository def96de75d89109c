"""Jit'd entry point for the wavefront expansion, with backend dispatch.

``wavefront_expand`` pads the frontier block to the kernel's row tiling and
dispatches to the Pallas kernel (``backend="pallas"``, interpret mode on
CPU) or the pure-jnp oracle (``backend="jnp"`` — same bits, no interpreter
overhead; the right choice for CPU-only runs, see docs/SAMPLER.md §3). The
engine calls this on *flattened* (P * N,) frontier blocks in sim mode and on
per-shard (N,) blocks under ``shard_map`` — draws are keyed by global vertex
id, so the flattening is invisible to the result.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.sampler.kernel import ROW_BLOCK, wavefront_expand_kernel
from repro.sampler.ref import wavefront_expand_ref


def wavefront_expand(
    vid: jnp.ndarray,  # (B,) int32 global vertex ids
    deg: jnp.ndarray,  # (B,) int32; < 0 marks invalid rows
    key: jnp.ndarray,  # (2,) uint32 folded 64-bit layer key (rng.fold_key_pair)
    fanout: int,
    *,
    backend: str = "pallas",
    interpret: bool,
) -> jnp.ndarray:
    """Slot codes (B, fanout) int32 (see ``ref`` for the encoding)."""
    key = jnp.asarray(key, jnp.uint32).reshape(1, 2)
    if backend == "jnp":
        return wavefront_expand_ref(vid, deg, key[0], fanout)
    if backend != "pallas":
        raise ValueError(f"unknown sampler backend {backend!r} (pallas | jnp)")
    B = vid.shape[0]
    pad = (-B) % ROW_BLOCK
    if pad:
        vid = jnp.concatenate([vid, jnp.zeros(pad, jnp.int32)])
        deg = jnp.concatenate([deg, jnp.full(pad, -1, jnp.int32)])
    codes = wavefront_expand_kernel(
        vid, deg, key, fanout=fanout, interpret=interpret
    )
    return codes[:B]
