"""Sharding rules: params / optimizer / batch / caches -> PartitionSpec trees.

Tensor parallelism over the ``model`` axis (attention heads, FFN hidden dim,
vocab, MoE experts, SSM inner dim); batch over ``("pod", "data")``. Scanned
block stacks get a leading unsharded layer axis. Dimensions that do not
divide the axis size (e.g. MQA kv=1 caches, Hymba's 50 SSM heads) are left
replicated — a documented cost, revisited in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, PartitionSpec as P

from repro.models.transformer.config import ArchConfig


def _last(path) -> str:
    for e in reversed(path):
        if hasattr(e, "key"):
            return str(e.key)
    return ""


def _path_keys(path) -> list[str]:
    return [str(e.key) for e in path if hasattr(e, "key")]


def _spec_for_leaf(keys: list[str], ndim: int, cfg: ArchConfig, axis_size: int):
    """PartitionSpec for one (unstacked) param leaf by name pattern."""
    if not keys:  # e.g. the optimizer step counter (NamedTuple field)
        return P(*([None] * ndim))
    name = keys[-1]
    shard = lambda *s: P(*s)  # noqa: E731

    if name == "embed":
        if cfg.num_codebooks:
            return shard(None, "model", None)
        return shard("model", None)
    if name == "lm_head":
        return shard(None, "model")
    if name in ("wq", "wk", "wv", "wq_b", "wkv_b"):
        return shard(None, "model")
    if name in ("wo",):
        return shard("model", None)
    if name in ("wq_a", "wkv_a", "router"):
        return shard(None, None)
    if name in ("w_in", "w_gate"):
        return shard("model", None, None) if ndim == 3 else shard(None, "model")
    if name == "w_out":
        return shard("model", None, None) if ndim == 3 else shard("model", None)
    if name == "in_proj":
        return shard(None, "model")
    if name == "out_proj":
        return shard("model", None)
    # norms, biases, A_log, D, dt_bias, scalar slots
    return P(*([None] * ndim))


def param_specs(cfg: ArchConfig, params_shapes, mesh) -> dict:
    """PartitionSpec pytree matching ``params_shapes`` (a ShapeDtypeStruct tree)."""
    axis = mesh.shape["model"]

    def build(path, leaf):
        keys = _path_keys(path)
        stacked = "blocks" in keys  # works for params and optimizer slots
        ndim = leaf.ndim - (1 if stacked else 0)
        spec = _spec_for_leaf(keys, ndim, cfg, axis)
        if stacked:
            spec = P(None, *spec)
        # drop shard axes that don't divide the dimension
        dims = leaf.shape
        fixed = []
        for i, s in enumerate(spec):
            if s is None:
                fixed.append(None)
            else:
                size = mesh.shape[s] if isinstance(s, str) else 1
                fixed.append(s if dims[i] % size == 0 else None)
        return P(*fixed)

    return jax.tree_util.tree_map_with_path(build, params_shapes)


def batch_specs(cfg: ArchConfig, batch_shapes, mesh) -> dict:
    """Batch inputs: leading batch dim over (pod, data) when it divides."""
    from repro.launch.mesh import data_axes

    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]

    def build(path, leaf):
        b = leaf.shape[0] if leaf.ndim else 1
        lead = dp if (leaf.ndim and b % dp_size == 0 and b > 1) else None
        return P(lead, *([None] * (leaf.ndim - 1))) if leaf.ndim else P()

    return jax.tree_util.tree_map_with_path(build, batch_shapes)


def cache_specs(cfg: ArchConfig, cache_shapes, mesh) -> dict:
    """KV/SSM caches: batch over (pod,data); head-like dims over model."""
    from repro.launch.mesh import data_axes

    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    model_size = mesh.shape["model"]

    def build(path, leaf):
        keys = _path_keys(path)
        stacked = keys and keys[0] == "scan"
        shape = leaf.shape[1:] if stacked else leaf.shape
        name = keys[-1]
        if name in ("k", "v"):  # (B, S, KV, hd)
            head_ok = shape[2] % model_size == 0
            # MQA (kv=1): fall back to sequence-sharding the cache over the
            # model axis (attention contracts S -> partial softmax + psum)
            seq_ok = (not head_ok) and shape[1] % model_size == 0
            spec = [
                dp if shape[0] % dp_size == 0 and shape[0] > 1 else None,
                "model" if seq_ok else None,
                "model" if head_ok else None,
                None,
            ]
        elif name in ("c_kv", "k_rope"):  # (B, S, latent) — MLA has no head
            # dim: sequence-shard the latent cache over the model axis
            spec = [
                dp if shape[0] % dp_size == 0 and shape[0] > 1 else None,
                "model" if shape[1] % model_size == 0 else None,
                None,
            ]
        elif name == "state":  # (B, H, N, P)
            spec = [
                dp if shape[0] % dp_size == 0 and shape[0] > 1 else None,
                "model" if shape[1] % model_size == 0 else None,
                None,
                None,
            ]
        else:
            spec = [None] * len(shape)
        if stacked:
            spec = [None] + spec
        return P(*spec)

    return jax.tree_util.tree_map_with_path(build, cache_shapes)


def make_split_mesh(
    num_replicas: int = 1,
    num_splits: int = 1,
    *,
    replica_axis: str = "replica",
    split_axis: str = "split",
    devices=None,
):
    """The 2D (replica, split) device mesh for hybrid split parallelism.

    Axis order is (R, P) with the split axis *minor*: on hardware whose
    device order follows interconnect locality (a TPU slice, one NVLink
    island per host), the P devices of one replica group are then physically
    adjacent, so the high-traffic channels — layer shuffles, cache remote
    fetch, sampler frontier exchange, all confined to ``split_axis`` —
    stay on the fast intra-group links while only the once-per-step
    gradient psum crosses the ``replica_axis`` (DESIGN.md §9). ``R == 1``
    degenerates to the 1D split mesh (the equivalence tests' anchor).

    Both axes are ``Auto``: the split path places every array through
    ``shard_map`` specs, and an ``Explicit`` axis (``jax.make_mesh``'s
    default) would demand a ``jax.set_mesh`` context around every jit and
    grad that touches the mesh.
    """
    if num_replicas < 1 or num_splits < 1:
        raise ValueError(
            f"mesh axes must be >= 1, got R={num_replicas} P={num_splits}"
        )
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        (num_replicas, num_splits), (replica_axis, split_axis),
        axis_types=(AxisType.Auto, AxisType.Auto), **kwargs
    )


def mesh_plan_specs(plan_arrays, replica_axis: str = "replica",
                    split_axis: str = "split") -> dict:
    """Per-replica-stacked plan arrays: shard leading (R, P) over the mesh.

    On the 2D mesh every plan/feature/label array carries a leading replica
    axis on top of the usual device axis — ``(R, P, ...)`` — built by
    stacking the R per-replica plans (each repadded to the shared
    high-water marks so the stack is rectangular). Sharding both leading
    axes gives each device exactly its replica's per-split slice, which is
    what the shard_map bodies consume.
    """
    return jax.tree_util.tree_map(
        lambda leaf: P(
            *((replica_axis, split_axis) + (None,) * (leaf.ndim - 2))
        ),
        plan_arrays,
    )


def split_cache_specs(cache_arrays, split_axis: str = "model") -> dict:
    """GNN split-parallel cache serving: shard on the leading device axis.

    The (P, C, F) resident feature-cache block and every ``CachePlan`` array
    carry the split/device dimension first (`owner` for ``send_slot``,
    `needer` for ``recv_pos``/``recv_mask``, the device itself for the
    rest), so under SPMD they all shard over the mesh's split axis on
    axis 0 and the per-shard slices are exactly what
    ``core.shuffle.spmd_serve_features`` consumes. ``split_axis`` defaults
    to the 1D launcher's ``"model"`` axis; pass ``"split"`` on the 2D
    ``make_split_mesh`` (the resident block is identical across replica
    groups, so the replica axis never appears in these specs).
    """
    return jax.tree_util.tree_map(
        lambda leaf: P(*((split_axis,) + (None,) * (leaf.ndim - 1))),
        cache_arrays,
    )


def replicated_block_specs(rep_arrays) -> dict:
    """Hot-vertex replication block: fully replicated on every device.

    The (R, F) resident block of replicated feature rows (and any companion
    arrays, e.g. the slot map) is the same on every split by construction —
    that is the whole point: replicated-src edges aggregate locally with
    zero wire bytes. Under SPMD the block therefore carries an all-``None``
    PartitionSpec, mirroring the ``owner``/``local_row`` maps in
    ``sampler_shard_specs`` — and on the 2D mesh the same all-``None``
    spec replicates it across both axes, no change needed.
    """
    return jax.tree_util.tree_map(
        lambda leaf: P(*((None,) * leaf.ndim)), rep_arrays
    )


def sampler_shard_specs(dev_arrays: dict, split_axis: str = "model") -> dict:
    """Device CSR shard sharding for SPMD cooperative sampling.

    The per-partition CSR blocks (``indptr``/``indices``/``edge_id``,
    leading axis P) and ``num_local`` shard over the mesh's split axis so
    each device holds only its own partition's adjacency; the O(V) ownership
    maps (``owner``/``local_row``) are replicated — every split must route
    any discovered vertex to its owner in O(1)
    (``repro.sampler.engine.sample_minibatch_spmd`` consumes the per-shard
    slices). ``split_axis`` defaults to the 1D launcher's ``"model"``
    axis; pass ``"split"`` on the 2D mesh — the CSR shards are the same
    for every replica group (one partition of one graph), so they too are
    replica-axis free.
    """
    replicated = ("owner", "local_row")
    return {
        k: (
            P(*((None,) * v.ndim))
            if k in replicated
            else P(*((split_axis,) + (None,) * (v.ndim - 1)))
        )
        for k, v in dev_arrays.items()
    }


def named(tree_specs, mesh):
    """PartitionSpec tree -> NamedSharding tree."""
    from jax.sharding import NamedSharding

    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
