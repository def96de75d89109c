"""Pallas TPU kernel: flash decode attention (one query token vs KV cache).

The decode-regime hot-spot is bandwidth: each step streams the whole cache
once. The kernel tiles the cache's sequence axis into VMEM blocks and keeps
the online-softmax state (running max / denominator / weighted accumulator)
in the revisited output blocks — the sequence-axis grid dimension is a
sequential accumulation, the TPU-idiomatic replacement for a CUDA
split-K + atomic reduction.

Grid = (B * KV, S / BS). Each step loads one (BS, D) key block and (BS, Dv)
value block plus the (G, D) query group (G = heads per KV head, MXU-aligned
by padding G*? -> the score matmul is (G x D) @ (D x BS)). Running state is
carried in three accumulator outputs aliased across grid steps and
finalized on the last block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _decode_body(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, d_ref, *, seq_block, scale):
    sj = pl.program_id(1)
    first = sj == 0

    q = q_ref[0]  # (G, D)
    k = k_ref[0]  # (BS, D)
    v = v_ref[0]  # (BS, Dv)
    cache_len = len_ref[0, 0]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (G, BS)
    pos = sj * seq_block + jax.lax.iota(jnp.int32, seq_block)
    s = jnp.where((pos < cache_len)[None, :], s, -1e30)

    m_prev = jnp.where(first, jnp.full_like(m_ref[0], -1e30), m_ref[0])  # (G, 1)
    d_prev = jnp.where(first, jnp.zeros_like(d_ref[0]), d_ref[0])
    o_prev = jnp.where(first, jnp.zeros_like(o_ref[0]), o_ref[0])

    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))  # (G, 1)
    p = jnp.exp(s - m_new)  # (G, BS)
    corr = jnp.exp(m_prev - m_new)  # (G, 1)
    d_new = d_prev * corr + p.sum(axis=1, keepdims=True)
    o_new = o_prev * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    m_ref[0] = m_new
    d_ref[0] = d_new
    is_last = sj == pl.num_programs(1) - 1
    o_ref[0] = jnp.where(is_last, o_new / jnp.maximum(d_new, 1e-30), o_new)


@functools.partial(jax.jit, static_argnames=("seq_block", "interpret"))
def decode_attention_pallas_bkv(
    q: jnp.ndarray,  # (BKV, G, D) query groups
    k: jnp.ndarray,  # (BKV, S, D)
    v: jnp.ndarray,  # (BKV, S, Dv)
    cache_len: jnp.ndarray,  # (BKV, 1) int32
    *,
    seq_block: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    BKV, G, D = q.shape
    S = k.shape[1]
    Dv = v.shape[2]
    assert S % seq_block == 0, (S, seq_block)
    nS = S // seq_block
    scale = 1.0 / np.sqrt(D)

    out, _, _ = pl.pallas_call(
        functools.partial(_decode_body, seq_block=seq_block, scale=scale),
        grid=(BKV, nS),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, s: (b, 0)),  # cache_len
            pl.BlockSpec((1, G, D), lambda b, s: (b, 0, 0)),
            pl.BlockSpec((1, seq_block, D), lambda b, s: (b, s, 0)),
            pl.BlockSpec((1, seq_block, Dv), lambda b, s: (b, s, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, G, Dv), lambda b, s: (b, 0, 0)),  # revisited
            pl.BlockSpec((1, G, 1), lambda b, s: (b, 0, 0)),  # running max
            pl.BlockSpec((1, G, 1), lambda b, s: (b, 0, 0)),  # running denom
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BKV, G, Dv), jnp.float32),
            jax.ShapeDtypeStruct((BKV, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((BKV, G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(cache_len, q, k, v)
    return out
