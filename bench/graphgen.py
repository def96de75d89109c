"""Seeded Orkut-shaped graph: RMAT edges with community locality.

The benchmark's own copy of the repository's ``orkut-s`` generator
(``repro.graph.datasets.make_dataset`` with ``rmat_edges``): the same
statistics (Graph500 RMAT quadrant probabilities, a fraction ``locality`` of
edges pulled inside their source's community block, symmetrised without self
loops or duplicates, features centred on a per-class mean with noise of
standard deviation 2), drawn on the device in one jitted call so that a run's
set-up stays short. It imports nothing of the program, so a change to the
program cannot move the graph a cell trains on.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class Graph:
    indptr: np.ndarray  # (N + 1,) int64; row v lists the in-neighbours of v
    indices: np.ndarray  # (nnz,) int32 source ids, ascending within a row
    features: np.ndarray  # (N, F) float32
    labels: np.ndarray  # (N,) int32
    train_ids: np.ndarray  # (T,) int64, in seeded order

    @property
    def num_nodes(self) -> int:
        return int(self.indptr.shape[0] - 1)

    @property
    def num_edges(self) -> int:
        return int(self.indices.shape[0])


def key_words(seed: int, salt: int) -> np.ndarray:
    """Two uint32 words of key material derived from ``(seed, salt)``.

    Any non-negative seed works, also one past 32 bits."""
    return np.random.SeedSequence([int(seed), salt]).generate_state(2)


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7, 8))
def _draw(words, num_nodes, num_edges, abcd, locality, communities, feat_dim,
          num_classes, num_train):
    key = jax.random.wrap_key_data(words, impl="threefry2x32")
    k_bits, k_loc, k_lab, k_ctr, k_noise, k_perm = jax.random.split(key, 6)
    a, b, c, d = abcd
    scale = max(int(np.ceil(np.log2(max(num_nodes, 2)))), 1)
    p_right = (b + d) / (a + b + c + d)
    u = jax.random.uniform(k_bits, (scale, 2, num_edges))
    dst_bit = (u[:, 0] < p_right).astype(jnp.int32)
    p_src1 = jnp.where(dst_bit == 1, d / (b + d), c / (a + c))
    src_bit = (u[:, 1] < p_src1).astype(jnp.int32)
    weights = (1 << jnp.arange(scale - 1, -1, -1, dtype=jnp.int32))[:, None]
    src = (src_bit * weights).sum(0) % num_nodes
    dst = (dst_bit * weights).sum(0) % num_nodes
    if locality > 0 and communities > 1:
        block = max(1, num_nodes // communities)
        local = jax.random.uniform(k_loc, (num_edges,)) < locality
        dst = jnp.where(local, (src // block) * block + dst % block, dst)
        dst = jnp.minimum(dst, num_nodes - 1)
    labels = jax.random.randint(k_lab, (num_nodes,), 0, num_classes, jnp.int32)
    centers = jax.random.normal(k_ctr, (num_classes, feat_dim), jnp.float32)
    noise = jax.random.normal(k_noise, (num_nodes, feat_dim), jnp.float32)
    features = centers[labels] + 2.0 * noise
    train_ids = jax.random.permutation(k_perm, num_nodes)[:num_train]
    return src, dst, labels, features, train_ids


def generate(cfg: dict, seed: int) -> Graph:
    """The configuration's graph for ``seed``: the same seed, the same graph."""
    n = int(cfg["num_nodes"])
    num_edges = int(n * float(cfg["avg_degree"]) / 2)
    num_train = max(1, int(n * float(cfg["train_fraction"])))
    src, dst, labels, features, train_ids = jax.device_get(_draw(
        key_words(seed, 0x6A7), n, num_edges, tuple(cfg["rmat_abcd"]),
        float(cfg["locality"]), int(cfg["num_communities"]),
        int(cfg["feat_dim"]), int(cfg["num_classes"]), num_train,
    ))
    indptr, indices = undirected_csr(np.asarray(src), np.asarray(dst), n)
    return Graph(
        indptr=indptr, indices=indices, features=np.asarray(features),
        labels=np.asarray(labels, np.int32),
        train_ids=np.asarray(train_ids, np.int64),
    )


def undirected_csr(src: np.ndarray, dst: np.ndarray, n: int):
    """Symmetrise ``src -> dst``, drop self loops and duplicates, and return
    the in-neighbour CSR ``(indptr, indices)``."""
    s = np.concatenate([src, dst]).astype(np.int64)
    d = np.concatenate([dst, src]).astype(np.int64)
    keep = s != d
    key = np.unique(d[keep] * n + s[keep])  # sorted by destination, then source
    rows, cols = key // n, key % n
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    return indptr, cols.astype(np.int32)
