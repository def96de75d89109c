"""Mixture-of-Experts layer (DeepSeek-V2 style: shared + routed top-k).

Routing uses capacity-bounded sorted dispatch — the static-shape TPU
formulation of "send computation to data": tokens are sorted by expert,
scattered into an (E, C) buffer, processed by expert-sharded weights (expert
parallelism over the ``model`` mesh axis -> all-to-all under GSPMD), and
combined back. Structurally this mirrors GSplit's split-parallel shuffle
(tokens = frontier vertices, experts = splits, router = f_G); see DESIGN.md §4.

Load-balance: auxiliary loss (mean gate entropy regularizer, Switch-style)
returned alongside the output; dropped tokens (over capacity) fall back to
the shared experts / residual path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.transformer.layers import mlp_apply, mlp_init


def _constrain(x, *axes):
    """Best-effort sharding hint (no-op outside a mesh context).

    Beyond-paper optimization (EXPERIMENTS.md §Perf): pinning the expert axis
    to the ``model`` mesh axis keeps dispatch/compute expert-local, so GSPMD
    emits one token-dim all-reduce per layer instead of all-gathering the
    full token buffer onto every device.
    """
    try:
        from jax.sharding import PartitionSpec as P

        return jax.lax.with_sharding_constraint(x, P(*axes))
    except Exception:  # no mesh context (single-device tests) — no-op
        return x


def _data_axes():
    """Mesh axes that shard the token dim, if a mesh context exists."""
    try:
        names = jax.sharding.get_abstract_mesh().axis_names
        dp = tuple(a for a in ("pod", "data") if a in names)
        return dp or None
    except Exception:
        return None


def moe_init(key, cfg, dtype) -> dict:
    d, dff = cfg.d_model, cfg.moe_d_ff
    E = cfg.num_experts
    keys = jax.random.split(key, 4)
    std_in = d**-0.5
    std_out = dff**-0.5
    gated = cfg.mlp_type in ("swiglu", "geglu")
    p = {
        "router": jax.random.normal(keys[0], (d, E), jnp.float32) * std_in,
        "w_in": jax.random.normal(keys[1], (E, d, dff), dtype) * std_in,
        "w_out": jax.random.normal(keys[2], (E, dff, d), dtype) * std_out,
    }
    if gated:
        p["w_gate"] = jax.random.normal(keys[3], (E, d, dff), dtype) * std_in
    if cfg.num_shared_experts:
        p["shared"] = mlp_init(
            keys[3], d, dff * cfg.num_shared_experts, cfg.mlp_type, dtype
        )
    return p


def moe_apply_shard_map(
    params: dict, x: jnp.ndarray, cfg
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-local dispatch under shard_map (§Perf A4, beyond-paper).

    pjit cannot shard the data-dependent global dispatch scatter, so GSPMD
    partially replicates the (E, C, d) buffers and all-reduces them every
    layer (measured: 46 TB/dev/step on deepseek-v2-236b train_4k). Here each
    (data i, model j) device routes its *own* token shard to its *own* E/|model|
    experts — GSplit's "send computation to data" applied to tokens — and the
    only collective is one token-dim psum over the model axis per layer.
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.moe_top_k
    model_size = mesh.shape["model"]
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    T_loc = B * S // dp_size
    E_loc = E // model_size
    C = int(np.ceil(T_loc * K / E * cfg.moe_capacity_factor))
    C = max(8, ((C + 7) // 8) * 8)

    def body(xt, router, w_gate, w_in, w_out):
        # xt: (T_loc, d) — this data shard's tokens, replicated over model
        gates = jax.nn.softmax(xt.astype(jnp.float32) @ router, axis=-1)
        topw, tope = jax.lax.top_k(gates, K)
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

        e_lo = jax.lax.axis_index("model") * E_loc
        flat_e = tope.reshape(-1) - e_lo  # local expert ids
        flat_t = jnp.repeat(jnp.arange(T_loc), K)
        flat_w = topw.reshape(-1)
        local = (flat_e >= 0) & (flat_e < E_loc)
        sort_key = jnp.where(local, flat_e, E_loc)
        order = jnp.argsort(sort_key)
        e_sorted = sort_key[order]
        t_sorted = flat_t[order]
        w_sorted = flat_w[order]
        starts = jnp.searchsorted(e_sorted, jnp.arange(E_loc))
        rank = jnp.arange(T_loc * K) - starts[e_sorted]
        keep = (e_sorted < E_loc) & (rank < C)
        slot = jnp.where(keep, e_sorted * C + rank, E_loc * C)

        buf = jnp.zeros((E_loc * C + 1, d), x.dtype)
        xe = buf.at[slot].set(xt[t_sorted])[:-1].reshape(E_loc, C, d)
        if cfg.mlp_type in ("swiglu", "geglu"):
            act = jax.nn.silu if cfg.mlp_type == "swiglu" else (
                lambda v: jax.nn.gelu(v, approximate=True)
            )
            gate = act(jnp.einsum("ecd,edf->ecf", xe, w_gate))
            hidden = gate * jnp.einsum("ecd,edf->ecf", xe, w_in)
        else:
            hidden = jax.nn.gelu(
                jnp.einsum("ecd,edf->ecf", xe, w_in), approximate=True
            )
        ye = jnp.einsum("ecf,efd->ecd", hidden, w_out)

        y_slots = jnp.concatenate(
            [ye.reshape(E_loc * C, d), jnp.zeros((1, d), x.dtype)], axis=0
        )
        y_tok = y_slots[slot] * w_sorted[:, None].astype(x.dtype)
        y = jax.ops.segment_sum(y_tok, t_sorted, num_segments=T_loc)
        # the ONLY cross-device exchange: combine expert partials
        y = jax.lax.psum(y, "model")

        me = gates.mean(axis=0)
        ce = (
            jnp.zeros(E).at[tope.reshape(-1)].add(flat_w).astype(jnp.float32)
            / T_loc
        )
        aux = (me * ce).sum() * E
        return y, aux[None]

    xt_all = x.reshape(B * S, d)
    gated = cfg.mlp_type in ("swiglu", "geglu")
    w_gate = params["w_gate"] if gated else params["w_in"]
    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(dp, None),
            P(None, None),  # router replicated
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(P(dp, None), P(dp)),
        check_vma=False,
    )(xt_all, params["router"], w_gate, params["w_in"], params["w_out"])
    out = y.reshape(B, S, d)
    if cfg.num_shared_experts:
        out = out + mlp_apply(
            params["shared"], xt_all, cfg.mlp_type
        ).reshape(B, S, d)
    return out, aux.mean()


def moe_apply(params: dict, x: jnp.ndarray, cfg) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (out, aux_loss)."""
    if getattr(cfg, "opt_moe_shard_map", False):
        try:
            return moe_apply_shard_map(params, x, cfg)
        except Exception:
            pass  # no mesh / indivisible E: fall through to the pjit path
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.moe_top_k
    T = B * S
    xt = x.reshape(T, d)
    dp = _data_axes() if cfg.opt_moe_shard_hints else None
    if dp:
        # token dim is batch-major: keep it data-sharded through dispatch
        xt = _constrain(xt, dp, None)

    gates = jax.nn.softmax(
        (xt.astype(jnp.float32) @ params["router"]), axis=-1
    )  # (T, E)
    topw, tope = jax.lax.top_k(gates, K)  # (T, K)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # ---- capacity-bounded sorted dispatch --------------------------------
    C = int(np.ceil(T * K / E * cfg.moe_capacity_factor))
    C = max(8, ((C + 7) // 8) * 8)
    flat_e = tope.reshape(-1)  # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = topw.reshape(-1)
    order = jnp.argsort(flat_e)  # group by expert
    e_sorted = flat_e[order]
    t_sorted = flat_t[order]
    w_sorted = flat_w[order]
    # rank within expert group
    starts = jnp.searchsorted(e_sorted, jnp.arange(E))
    rank = jnp.arange(T * K) - starts[e_sorted]
    keep = rank < C
    slot = jnp.where(keep, e_sorted * C + rank, E * C)  # overflow -> trash slot

    buf = jnp.zeros((E * C + 1, d), x.dtype)
    xe = buf.at[slot].set(xt[t_sorted])[:-1].reshape(E, C, d)
    if cfg.opt_moe_shard_hints:
        xe = _constrain(xe, "model", None, None)

    # ---- expert compute (E sharded over the model axis) ------------------
    if cfg.mlp_type in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else (
            lambda v: jax.nn.gelu(v, approximate=True)
        )
        gate = act(jnp.einsum("ecd,edf->ecf", xe, params["w_gate"]))
        hidden = gate * jnp.einsum("ecd,edf->ecf", xe, params["w_in"])
    else:
        hidden = jax.nn.gelu(
            jnp.einsum("ecd,edf->ecf", xe, params["w_in"]), approximate=True
        )
    ye = jnp.einsum("ecf,efd->ecd", hidden, params["w_out"])  # (E, C, d)
    if cfg.opt_moe_shard_hints:
        ye = _constrain(ye, "model", None, None)

    # ---- combine ----------------------------------------------------------
    y_slots = jnp.concatenate(
        [ye.reshape(E * C, d), jnp.zeros((1, d), x.dtype)], axis=0
    )
    y_tok = y_slots[slot] * w_sorted[:, None].astype(x.dtype)  # (T*K, d)
    out = jax.ops.segment_sum(y_tok, t_sorted, num_segments=T)

    if dp:
        out = _constrain(out, dp, None)
    if cfg.num_shared_experts:
        out = out + mlp_apply(params["shared"], xt, cfg.mlp_type)

    # Switch-style load-balance aux loss
    me = gates.mean(axis=0)  # (E,)
    ce = jnp.zeros(E).at[flat_e].add(flat_w).astype(jnp.float32) / T
    aux = (me * ce).sum() * E

    return out.reshape(B, S, d), aux
