"""GraphSAGE, mean aggregator: weights, one layer and a step's operations.

GraphSAGE (Hamilton et al., arXiv:1706.02216, Alg. 1 with the concatenation
written as two matrices), for destination ``v`` and its sampled in-neighbours
``N(v)``:

    h_v' = h_v W_self + mean_{u in N(v)} h_u W_neigh + b

The parameter layout is the program's (``models/gnn/layers.py``): per layer
``w_self`` and ``w_neigh`` of (d_in, d_out) and ``b`` of (d_out,).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import layers
from bench.graphgen import key_words
from bench.reference import layer_dims


def init_params(cfg: dict, seed: int) -> list[dict]:
    """Glorot-uniform weights and zero biases from ``seed``, made on the
    device in one jitted call."""
    return _init(key_words(seed, 0x3C1), tuple(layer_dims(cfg)))


@partial(jax.jit, static_argnums=(1,))
def _init(words, dims):
    key = jax.random.wrap_key_data(words, impl="threefry2x32")

    def glorot(k, shape):
        lim = float(np.sqrt(6.0 / (shape[-2] + shape[-1])))
        return jax.random.uniform(k, shape, jnp.float32, -lim, lim)

    params = []
    for d_in, d_out in dims:
        key, k1, k2, _ = jax.random.split(key, 4)
        params.append({
            "w_self": glorot(k1, (d_in, d_out)),
            "w_neigh": glorot(k2, (d_in, d_out)),
            "b": jnp.zeros((d_out,), jnp.float32),
        })
    return params


def layer(p, h, src, dst, self_idx, n, dtype):
    """One layer into ``n`` destinations: ``h`` holds the source rows,
    ``(src, dst)`` the sampled edges and ``self_idx`` each destination's own
    row; padded edges point at ``n`` and drop out of the sums."""
    total = jax.ops.segment_sum(h[src], dst, n)
    count = jax.ops.segment_sum(jnp.ones(dst.shape, dtype), dst, n)
    agg = total / jnp.maximum(count, 1)[:, None]
    return h[self_idx] @ p["w_self"] + agg @ p["w_neigh"] + p["b"]


def step_flops(cfg: dict, sizes: list[dict]) -> float:
    """Operations of the layers in one training step, forward and backward
    (``bench/counts.py`` states the conventions and adds the loss)."""
    total = 0.0
    for s, d_in, d_out, is_input, is_last in layers(cfg, sizes):
        n, e = s["n_dst"], s["edges"]
        act = 0 if is_last else n * d_out  # ReLU
        agg = e * d_in + n * d_in  # sum over edges, divide by count
        mm = 2 * 2 * n * d_in * d_out  # h_self @ W_self, agg @ W_neigh
        fwd = agg + mm + n * d_out + act
        bwd = mm + n * d_out + act  # weight gradients, bias
        if not is_input:
            bwd += mm + agg  # input gradients
        total += fwd + bwd
    return total
