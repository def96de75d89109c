"""Plain reference of a cell's training step, for any model.

Straight ``jax.numpy`` over one sampled block's edge lists: no plan, no
padding to the program's buckets, no split, shuffle or kernel. A model's
layer equations, parameter layout and weights are in
``bench/models/<model>.py`` (``init_params``, ``layer``), found by the
configuration's ``model`` key; this file holds what every model shares.

ReLU follows every layer but the last; the loss is the mean softmax cross
entropy over the batch's targets. Adam (Kingma & Ba, Alg. 1) with the
configuration's ``lr``, ``b1``, ``b2``, ``eps``.

Weights are made by the model's module from the seed (``init_params``) and
handed to the program, so the reference takes nothing the program made.
Blocks are the sampled mini-batches in global vertex ids; ``block_arrays``
checks each one against the benchmark's own graph before the reference uses
it.

The reference runs in float32 at the matmul precision that the
configuration states (``matmul_precision``: on a TPU ``default`` is one
bfloat16 pass with float32 accumulation and storage; ``highest`` is full
float32). ``dtype="bfloat16"`` is the control: the same step computed in
bfloat16 (the next precision below the configuration's float32), with
float32 master weights and optimizer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import registry
from bench.graphgen import Graph


def layer_dims(cfg: dict) -> list[tuple[int, int]]:
    dims, d_in = [], int(cfg["feat_dim"])
    for i in range(int(cfg["num_layers"])):
        last = i == int(cfg["num_layers"]) - 1
        d_out = int(cfg["num_classes"]) if last else int(cfg["hidden_dim"])
        dims.append((d_in, d_out))
        d_in = d_out
    return dims


def model(cfg: dict):
    """The module ``bench/models/<model>.py`` of the configuration's model."""
    return registry.load("models", cfg["model"])


def init_params(cfg: dict, seed: int) -> list[dict]:
    """The model's weights from ``seed``, made on the device in one jitted
    call, in the layout the program takes."""
    return model(cfg).init_params(cfg, seed)


# --------------------------------------------------------------------------- #
# blocks
# --------------------------------------------------------------------------- #
class BlockError(ValueError):
    """A sampled block that is not a neighbour sample of the graph."""


def block_arrays(block: dict, graph: Graph, fanouts, batch_size: int) -> dict:
    """Local index arrays of one sampled block, after checking it.

    ``block["frontiers"][k]`` are the vertices at depth ``k`` (0 = targets)
    and ``block["layers"][k] = (src, dst)`` the sampled edges into depth
    ``k``. The check: the targets are ``batch_size`` distinct training
    vertices; every edge is an edge of the graph (or the self loop of a
    vertex with no neighbours); a vertex keeps all its in-edges when it has
    at most ``fanout`` of them and between 1 and ``fanout`` distinct ones
    otherwise; each frontier is the one below it joined with its sources.
    """
    fr = [np.asarray(f, np.int64) for f in block["frontiers"]]
    n = graph.num_nodes
    if fr[0].size != batch_size or np.unique(fr[0]).size != fr[0].size:
        raise BlockError(f"targets: {fr[0].size} given, {batch_size} distinct wanted")
    if not np.isin(fr[0], graph.train_ids).all():
        raise BlockError("a target is not a training vertex")
    deg = np.diff(graph.indptr)
    rows = np.repeat(np.arange(n, dtype=np.int64), deg)
    edge_keys = rows * n + graph.indices.astype(np.int64)  # sorted
    out = {"src": [], "dst": [], "self": [], "n": [len(f) for f in fr]}
    for k, (src, dst) in enumerate(block["layers"]):
        src, dst = np.asarray(src, np.int64), np.asarray(dst, np.int64)
        keys = dst * n + src
        pos = np.minimum(np.searchsorted(edge_keys, keys), edge_keys.size - 1)
        loop = (src == dst) & (deg[dst] == 0)
        if not ((edge_keys[pos] == keys) | loop).all():
            raise BlockError(f"layer {k}: a sampled edge is not in the graph")
        if np.unique(keys).size != keys.size:
            raise BlockError(f"layer {k}: an edge is sampled twice")
        d_sorted = np.sort(fr[k])
        if not (np.isin(dst, d_sorted).all()):
            raise BlockError(f"layer {k}: an edge ends outside the frontier")
        got = np.bincount(np.searchsorted(d_sorted, dst), minlength=d_sorted.size)
        want_all = np.maximum(deg[d_sorted], 1)
        small = want_all <= fanouts[k]
        if not ((got == want_all) | (~small & (got >= 1) & (got <= fanouts[k]))).all():
            raise BlockError(f"layer {k}: a vertex has the wrong number of edges")
        if not np.array_equal(np.unique(np.concatenate([fr[k], src])), np.sort(fr[k + 1])):
            raise BlockError(f"layer {k}: frontier {k + 1} is not frontier {k} with its sources")
        out["src"].append(_find(src, fr[k + 1]))
        out["dst"].append(_find(dst, fr[k]))
        out["self"].append(_find(fr[k], fr[k + 1]))
    out["inputs"] = fr[-1]
    out["targets"] = fr[0]
    return out


def _find(ids: np.ndarray, within: np.ndarray) -> np.ndarray:
    """Positions of ``ids`` in ``within`` (every id is known to be there)."""
    order = np.argsort(within, kind="stable")
    return order[np.searchsorted(within[order], ids)]


def _pad_blocks(arrs: list[dict]) -> tuple[list[dict], tuple]:
    """Pad the blocks' arrays to common sizes so one compiled step serves all.

    Padded edges point past the last destination (dropped by the segment
    reductions); padded vertices are never a source of a true edge, and
    padded targets are masked out of the loss."""
    L = len(arrs[0]["src"])
    n_max = [max(a["n"][d] for a in arrs) for d in range(L + 1)]
    e_max = [max(a["src"][k].size for a in arrs) for k in range(L)]
    out = []
    for a in arrs:
        p = {"src": [], "dst": [], "self": []}
        for k in range(L):
            e = a["src"][k].size
            p["src"].append(np.pad(a["src"][k], (0, e_max[k] - e)).astype(np.int32))
            p["dst"].append(np.pad(a["dst"][k], (0, e_max[k] - e),
                                   constant_values=n_max[k]).astype(np.int32))
            p["self"].append(np.pad(a["self"][k], (0, n_max[k] - a["n"][k])).astype(np.int32))
        p["inputs"] = np.pad(a["inputs"], (0, n_max[L] - a["n"][L]))
        p["targets"] = np.pad(a["targets"], (0, n_max[0] - a["n"][0]))
        p["mask"] = np.arange(n_max[0]) < a["n"][0]
        out.append(p)
    return out, tuple(n_max)


# --------------------------------------------------------------------------- #
# the step
# --------------------------------------------------------------------------- #
def forward(params, x, blk, sizes, dtype, layer):
    """Logits of the padded targets of one block, through the model's
    ``layer``."""
    h = x.astype(dtype)
    L = len(params)
    for j, p in enumerate(params):
        k = L - 1 - j  # params[0] consumes the input features
        p = jax.tree_util.tree_map(lambda a: a.astype(dtype), p)
        h = layer(p, h, blk["src"][k], blk["dst"][k], blk["self"][k], sizes[k], dtype)
        if j < L - 1:
            h = jax.nn.relu(h)
    return h


def loss_fn(params, x, labels, mask, blk, sizes, dtype, layer):
    logits = forward(params, x, blk, sizes, dtype, layer).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(mask, nll, 0.0)) / jnp.sum(mask)


def adam_update(cfg: dict, params, grads, m, v, t):
    b1, b2 = float(cfg["adam_b1"]), float(cfg["adam_b2"])
    lr, eps = float(cfg["lr"]), float(cfg["adam_eps"])
    m = jax.tree_util.tree_map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree_util.tree_map(
        lambda p, m_, v_: p - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + eps), params, m, v
    )
    return params, m, v


def run_steps(cfg: dict, params0, blocks: list[dict], graph: Graph, fanouts,
              batch_size: int, *, precision: str, dtype: str = "float32") -> dict:
    """Train ``params0`` for one step per block, as the program's first steps,
    computed in ``dtype`` with matmuls at ``precision``.

    Returns the loss of each step, the first step's gradient and the
    parameters after the last step, all on the host."""
    arrs = [block_arrays(b, graph, fanouts, batch_size) for b in blocks]
    padded, sizes = _pad_blocks(arrs)
    cdt = jnp.dtype(dtype)
    layer = model(cfg).layer

    @jax.jit
    def step(params, m, v, t, x, labels, mask, blk):
        with jax.default_matmul_precision(precision):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, x, labels, mask, blk, sizes, cdt, layer)
            )(params)
            params, m, v = adam_update(cfg, params, grads, m, v, t)
        return params, m, v, loss, grads

    params = jax.tree_util.tree_map(jnp.asarray, params0)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad1 = [], None
    for t, p in enumerate(padded, start=1):
        blk = {k: [jnp.asarray(x) for x in p[k]] for k in ("src", "dst", "self")}
        x = jnp.asarray(graph.features[p["inputs"]])
        labels = jnp.asarray(graph.labels[p["targets"]])
        params, m, v, loss, grads = step(
            params, m, v, jnp.float32(t), x, labels, jnp.asarray(p["mask"]), blk
        )
        losses.append(float(loss))
        if grad1 is None:
            grad1 = jax.device_get(grads)
        del x, blk, grads
    return {"losses": losses, "grad1": grad1, "params": jax.device_get(params)}
