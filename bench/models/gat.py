"""GAT, multi-head attention: weights, one layer and a step's operations.

Graph Attention Networks (Veličković et al., arXiv:1710.10903), the
three-layer inductive model of §3.3 (the PPI set-up): two hidden attention
layers of K heads, concatenated, a skip connection across the intermediate
attentive layer, and an output layer whose heads are averaged (eq. 6). For
destination ``v``, its sampled in-neighbours ``N(v)`` and heads ``k``:

    z_u^k     = h_u W^k
    e_uv^k    = LeakyReLU_0.2(a_src^k . z_u^k + a_dst^k . z_v^k)
    alpha_uv^k = softmax over u in N(v) of e_uv^k
    hidden:  h_v' = concat_k sum_u alpha_uv^k z_u^k  [+ h_v]  + b
    output:  h_v' = mean_k   sum_u alpha_uv^k z_u^k           + b

The skip ``h_v`` (the destination's own input row) is added where a layer
concatenates its heads and its input is as wide as its output, before the
bias and the activation (DGL's order; the paper gives none). At the
configuration's widths that is the middle layer alone.

Departures from the paper, each also in the configuration's ``assumed``:

* ReLU, not ELU, after every layer but the last: ``bench/reference.forward``
  applies ReLU for every model.
* The output layer has as many heads as the hidden ones (the program takes
  one head count), where the PPI model has 6.
* Softmax cross-entropy over single labels, not sigmoid over 121.
* No self-loop is added: attention runs over the sampled in-edges.
* No dropout, as in the paper's PPI run.

The parameter layout is the program's (``models/gnn/layers.py``): per layer
``w`` of (d_in, H, d_h), ``a_src`` and ``a_dst`` of (H, d_h), and ``b`` of
(H * d_h,) where the layer concatenates its heads or (d_h,) where it
averages them. Here the layer reads which from the shapes: a bias one head
wide averages (with one head the two are the same).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import layers
from bench.graphgen import key_words
from bench.reference import layer_dims

SLOPE = 0.2  # LeakyReLU's negative slope (§2.1)


def head_dims(cfg: dict) -> list[tuple[int, int, int, bool]]:
    """``(d_in, H, d_h, averaged)`` per layer, input layer first: the hidden
    layers split their width over the heads, the output layer gives each
    head the whole class width and averages them."""
    H = int(cfg["num_heads"])
    dims = layer_dims(cfg)
    out = []
    for j, (d_in, d_out) in enumerate(dims):
        last = j == len(dims) - 1
        if not last and d_out % H:
            raise ValueError(f"gat: hidden width {d_out} does not split over {H} heads")
        out.append((d_in, H, d_out if last else d_out // H, last and H > 1))
    return out


def init_params(cfg: dict, seed: int) -> list[dict]:
    """Glorot-uniform weights and zero biases from ``seed``, made on the
    device in one jitted call."""
    return _init(key_words(seed, 0x6A7), tuple(head_dims(cfg)))


@partial(jax.jit, static_argnums=(1,))
def _init(words, dims):
    key = jax.random.wrap_key_data(words, impl="threefry2x32")

    def uniform(k, shape, fan_in, fan_out):
        lim = float(np.sqrt(6.0 / (fan_in + fan_out)))
        return jax.random.uniform(k, shape, jnp.float32, -lim, lim)

    params = []
    for d_in, H, dh, averaged in dims:
        key, k1, k2, k3 = jax.random.split(key, 4)
        params.append({
            # the projection maps d_in to all heads at once; each attention
            # vector maps one head's d_h features to one score
            "w": uniform(k1, (d_in, H, dh), d_in, H * dh),
            "a_src": uniform(k2, (H, dh), dh, 1),
            "a_dst": uniform(k3, (H, dh), dh, 1),
            "b": jnp.zeros((dh if averaged else H * dh,), jnp.float32),
        })
    return params


def layer(p, h, src, dst, self_idx, n, dtype):
    """One layer into ``n`` destinations: ``h`` holds the source rows,
    ``(src, dst)`` the sampled edges and ``self_idx`` each destination's own
    row; padded edges point at ``n`` and drop out of the softmax and the
    sums."""
    d_in, H, dh = p["w"].shape
    valid = (dst < n)[:, None]
    at = jnp.minimum(dst, n - 1)  # a padded edge reads a real row, then is masked
    z = jnp.einsum("mf,fhd->mhd", h, p["w"])  # (M, H, d_h)
    # each score an elementwise product and a sum over the head's features,
    # in the rows' own precision: not a matmul, whose rounding at the TPU's
    # default precision XLA chooses by shape
    s_src = (z * p["a_src"]).sum(axis=-1)  # (M, H)
    s_dst = (z[self_idx] * p["a_dst"]).sum(axis=-1)  # (n, H)
    # (E, H) logits; a padded edge's is -inf, so its weight is exp(-inf) = 0
    e = jnp.where(valid, jax.nn.leaky_relu(s_src[src] + s_dst[at], SLOPE), -jnp.inf)
    # softmax over each destination's edges, shifted by its largest logit
    # (a padded destination has no edge, so no largest logit: shift by 0)
    top = jax.ops.segment_max(e, dst, n)
    top = jax.lax.stop_gradient(jnp.where(jnp.isfinite(top), top, 0))
    ex = jnp.exp(e - top[at])
    alpha = ex / jnp.where(valid, jax.ops.segment_sum(ex, dst, n)[at], 1)
    heads = jax.ops.segment_sum(alpha[:, :, None] * z[src], dst, n)  # (n, H, d_h)
    if p["b"].shape[0] != H * dh:
        return heads.mean(axis=1) + p["b"]
    out = heads.reshape(n, H * dh)
    if d_in == H * dh:
        out = out + h[self_idx]
    return out + p["b"]


def step_flops(cfg: dict, sizes: list[dict]) -> float:
    """Operations of the layers in one training step, forward and backward
    (``bench/counts.py`` states the conventions and adds the loss).

    Per layer of ``E`` edges into ``n`` destinations from ``n_src`` rows:
    the projection over the ``n_src`` rows (2 d_in H d_h each), both score
    projections (2 H d_h a row, over ``n_src`` and ``n`` rows), E H for the
    logits' add and E H for the LeakyReLU, 5 E H for the softmax (max,
    shift, exp, sum, divide), 2 E H d_h for the weighted sum, then per
    destination the head mean (H d_h), the skip (H d_h) where the layer has
    one, the bias and the ReLU (one each per output). The backward pass is
    counted as twice the forward, less the projection's input gradient
    (2 n_src d_in H d_h) on the input layer, which never computes it.
    """
    total = 0.0
    for (s, _, d_out, is_input, is_last), (d_in, H, dh, averaged) in zip(
            layers(cfg, sizes), head_dims(cfg)):
        n, n_src, e = s["n_dst"], s["n_src"], s["edges"]
        proj = 2 * n_src * d_in * H * dh
        scores = 2 * (n_src + n) * H * dh
        attention = (2 + 5) * e * H + 2 * e * H * dh
        per_dst = n * H * dh if averaged or d_in == H * dh else 0  # mean or skip
        per_dst += n * d_out * (1 if is_last else 2)  # bias, ReLU
        fwd = proj + scores + attention + per_dst
        total += 3 * fwd - (proj if is_input else 0)
    return total
