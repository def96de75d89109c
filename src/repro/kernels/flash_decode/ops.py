"""Jit'd wrapper: (B, H, D) GQA decode -> per-(batch, kv-head) kernel layout."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_decode.kernel import decode_attention_pallas_bkv


def decode_attention_pallas(
    q: jnp.ndarray,  # (B, H, D)
    k: jnp.ndarray,  # (B, S, KV, D)
    v: jnp.ndarray,  # (B, S, KV, Dv)
    cache_len,  # scalar int32
    seq_block: int = 512,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Single-token GQA decode attention -> (B, H, Dv).

    Contract (docs/KERNELS.md): ``H`` must be a multiple of ``KV`` (group
    size G = H // KV); cache positions >= ``cache_len`` are masked out of
    the softmax, so stale KV-cache tail values are irrelevant. ``cache_len``
    may be a traced scalar — the op is jit-safe. Softmax/accumulation run
    in f32; output is cast back to ``q.dtype``.
    """
    B, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    Dv = v.shape[3]
    G = H // KV

    qg = q.reshape(B, KV, G, D).reshape(B * KV, G, D)
    kg = k.transpose(0, 2, 1, 3).reshape(B * KV, S, D)
    vg = v.transpose(0, 2, 1, 3).reshape(B * KV, S, Dv)
    lens = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(1, 1), (B * KV, 1)
    )
    out = decode_attention_pallas_bkv(
        qg, kg, vg, lens, seq_block=seq_block, interpret=interpret
    )  # (B*KV, G, Dv) f32
    return out.reshape(B, KV, G, Dv).reshape(B, H, Dv).astype(q.dtype)
