"""Segment operations — the GNN aggregation hot-spot, with backend dispatch.

``backend='jnp'``    pure-jnp (XLA scatter-add) reference path, used by default
                     on CPU and as the oracle for the Pallas kernels.
``backend='pallas'`` TPU Pallas kernels operating on the dst-block-packed
                     layout; validated in interpret mode. The ops here pack
                     host-side, so ``dst``/``mask`` must be *concrete*
                     (numpy) — fine for offline/bench call sites. Inside
                     jit (the training step), use the fused
                     ``kernels.gather_segsum`` ops, which consume the
                     plan-carried layout instead (docs/KERNELS.md).

Contract shared by all ops (see docs/KERNELS.md for the full statement):
``dst (E,) int32`` holds a destination row in ``[0, num_out)`` for every
edge slot, including padding; ``mask (E,) bool`` marks the valid slots.
Destinations whose incident edges are all masked out ("empty segments")
yield *exact zeros* — never NaN — in every op and dtype, including float16,
where the old ``-1e30`` max-clamp constant overflowed to ``-inf`` and
poisoned the softmax via ``exp(-inf - -inf) * 0 == nan``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import pallas_interpret


def segment_sum(contrib, dst, mask, num_out, backend="jnp"):
    """Masked per-destination sum of ``contrib (E, F)`` -> ``(num_out, F)``.

    Masked slots contribute exactly 0.0; empty segments are exact zeros.
    Output dtype == ``contrib.dtype``.
    """
    if backend == "pallas":
        from repro.kernels.segsum import ops as segsum_ops

        return segsum_ops.segment_sum_pallas(
            contrib, dst, mask, num_out, interpret=pallas_interpret()
        )
    w = mask.astype(contrib.dtype)
    return jax.ops.segment_sum(contrib * w[:, None], dst, num_segments=num_out)


def segment_mean(contrib, dst, mask, num_out, backend="jnp"):
    """Masked per-destination mean -> ``(num_out, F)``.

    The denominator is counted in float32 regardless of ``contrib.dtype``
    (low-precision dtypes cannot represent counts > 256 exactly) and clamped
    to 1, so empty segments return exact zeros rather than 0/0.
    """
    total = segment_sum(contrib, dst, mask, num_out, backend=backend)
    count = jax.ops.segment_sum(
        mask.astype(jnp.float32), dst, num_segments=num_out
    )
    return total / jnp.maximum(count, 1.0).astype(total.dtype)[:, None]


def edge_softmax(logits, dst, mask, num_out, backend="jnp"):
    """Per-destination softmax over incoming edges: ``(E, H) -> (E, H)``.

    Masked edges get weight exactly 0.0 and take no part in the
    normalization; a destination whose edges are all masked contributes
    only zeros. NaN-safe in every float dtype: the mask is applied with
    ``where`` (a ``*`` would propagate NaN from dead lanes) and the
    empty-segment clamp uses a finite value of the *input* dtype instead
    of a hard-coded ``-1e30`` (which is ``-inf`` in float16).
    """
    if backend == "pallas":
        from repro.kernels.edge_softmax import ops as es_ops

        return es_ops.edge_softmax_pallas(
            logits, dst, mask, num_out, interpret=pallas_interpret()
        )
    neg = jnp.asarray(jnp.finfo(logits.dtype).min / 2, logits.dtype)
    masked = jnp.where(mask[:, None], logits, neg)
    seg_max = jax.ops.segment_max(masked, dst, num_segments=num_out)
    seg_max = jnp.maximum(seg_max, neg)  # empty segments: -inf -> finite
    ex = jnp.where(mask[:, None], jnp.exp(masked - seg_max[dst]), 0.0)
    denom = jax.ops.segment_sum(ex, dst, num_segments=num_out)
    tiny = jnp.asarray(jnp.finfo(logits.dtype).tiny, logits.dtype)
    return ex / jnp.maximum(denom[dst], tiny)
