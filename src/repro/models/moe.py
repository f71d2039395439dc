"""DeepSeek-style Mixture-of-Experts FFN (fine-grained + shared experts).

DeepSeekMoE (arXiv:2401.06066): many small routed experts (top-6 of 64 at
expert d_ff 1408) plus always-on shared experts; first ``first_k_dense``
layers stay dense.  Routing is softmax -> top-k (optionally renormalized),
with the standard switch-style load-balance auxiliary loss.

Dispatch is the sort-based capacity implementation (MaxText/GShard "dropping"
style, but without the (T, E) one-hot): ranks-within-expert come from an
argsort + run-start subtraction, tokens scatter into an (E, C, d) buffer that
is sharded over the ``model`` axis (expert parallelism), expert FFNs run as a
batched einsum against E-sharded weights, and the combine scatter-adds back to
token space (GSPMD turns that into a reduce over the expert axis).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding_rules import NULL_CTX, ShardingCtx
from repro.models.layers import _init_dense


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 64
    top_k: int = 6
    d_ff_expert: int = 1408
    n_shared: int = 2
    first_k_dense: int = 1
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.001
    normalize_topk: bool = False
    routed_scaling: float = 1.0


def moe_init(key, d_model: int, cfg: MoEConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    E, f = cfg.num_experts, cfg.d_ff_expert
    p = {
        "router": _init_dense(ks[0], (d_model, E), jnp.float32, scale=0.02),
        "w_gate": _init_dense(ks[1], (E, d_model, f), dtype),
        "w_up": _init_dense(ks[2], (E, d_model, f), dtype),
        "w_down": _init_dense(ks[3], (E, f, d_model), dtype),
    }
    if cfg.n_shared:
        from repro.models.layers import mlp_init

        p["shared"] = mlp_init(ks[4], d_model, cfg.n_shared * f, dtype)
    return p


def _ranks_within_expert(flat_e: jnp.ndarray, num_experts: int):
    """rank[i] = #earlier assignments with the same expert id.  Sort-based:
    no (T*k, E) one-hot materialization."""
    tk = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    idx = jnp.arange(tk, dtype=jnp.int32)
    change = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_e[1:] != sorted_e[:-1]]
    )
    run_start = jax.lax.associative_scan(jnp.maximum, jnp.where(change, idx, 0))
    rank_sorted = idx - run_start
    rank = jnp.zeros((tk,), jnp.int32).at[order].set(rank_sorted)
    return rank


def moe_apply(
    params,
    cfg: MoEConfig,
    x: jnp.ndarray,
    *,
    ctx: ShardingCtx = NULL_CTX,
    capacity_factor: float = 0.0,
):
    """x (B, S, d) -> (out (B, S, d), aux_loss scalar).

    capacity_factor overrides cfg (0 = use config).  Tokens beyond an
    expert's capacity are dropped for that expert (they keep their other
    top-k routes and the shared experts).

    On a mesh, the routed-expert interior runs under shard_map
    (``_moe_routed_shard_map``): GSPMD's handling of the pjit-constrained
    dispatch all-gathered the (E, C, d) token buffers and the routing index
    arrays globally (~2.4 GiB/layer of avoidable collectives on
    deepseek-moe-16b train_4k); the explicit schedule computes routing
    replicated per model column, dispatches only to local experts, and
    combines with ONE psum of (T, d) partials.
    """
    if ctx.mesh is not None and not _JUST_LOCAL:
        routed, aux = _moe_routed_shard_map(
            params, cfg, x, ctx, capacity_factor
        )
        if "shared" in params:
            from repro.models.layers import mlp_apply

            routed = routed + mlp_apply(
                params["shared"], x.reshape(-1, x.shape[-1])
            ).reshape(x.shape)
        return routed, aux
    return _moe_apply_local(params, cfg, x, ctx, capacity_factor)


_JUST_LOCAL = False  # test hook


def _moe_routed_shard_map(params, cfg, x, ctx: ShardingCtx, capacity_factor):
    """Expert-parallel routed experts via an explicit shard_map schedule."""
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    expert_axes = tuple(
        a for a in ctx.rules.get("expert", ()) if a in mesh.shape
    )
    batch_axes = tuple(
        a for a in ctx.rules.get("batch", ()) if a in mesh.shape
    )
    n_batch_lanes = 1
    for a in batch_axes:
        n_batch_lanes *= mesh.shape[a]
    if x.shape[0] % max(n_batch_lanes, 1):
        batch_axes = ()  # tiny batch (long-decode B=1): replicate tokens
    if not expert_axes:
        return _moe_apply_local(
            params, cfg, x, ctx, capacity_factor, include_shared=False,
        )
    other_axes = tuple(
        a for a in mesh.shape if a not in expert_axes + batch_axes
    )
    ep = 1
    for a in expert_axes:
        ep *= mesh.shape[a]
    E_loc = cfg.num_experts // ep
    routed_params = {
        "router": params["router"],
        "w_gate": params["w_gate"],
        "w_up": params["w_up"],
        "w_down": params["w_down"],
    }
    x_spec = P(batch_axes, None, None) if batch_axes else P(None, None, None)
    in_specs = (
        {
            "router": P(),
            "w_gate": P(expert_axes, None, None),
            "w_up": P(expert_axes, None, None),
            "w_down": P(expert_axes, None, None),
        },
        x_spec,
    )

    def local_moe(p, x_loc):
        e0 = jnp.int32(0)
        stride = E_loc
        for a in reversed(expert_axes):
            e0 = e0 + jax.lax.axis_index(a) * stride
            stride = stride * mesh.shape[a]
        out, aux = _routed_core(
            p, cfg, x_loc, capacity_factor, e0=e0, E_loc=E_loc
        )
        out = jax.lax.psum(out, expert_axes)  # combine expert partials
        if other_axes:
            out = jax.lax.pmean(out, other_axes)
        aux = jax.lax.pmean(aux, batch_axes) if batch_axes else aux
        return out, aux

    out, aux = jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(x_spec, P()),
        check_vma=False,
    )(routed_params, x)
    return out, aux


def _routed_core(params, cfg: MoEConfig, x, capacity_factor, *, e0, E_loc):
    """Routing + dispatch + expert FFN for the local expert slice.

    x (B_loc, S, d); params expert weights already sliced (E_loc, ...).
    Returns the PARTIAL output (only local experts' contributions).
    """
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.top_k
    T = B * S
    cf = capacity_factor or cfg.capacity_factor
    C = T if cf < 0 else int(np.ceil(T * K / E * cf))
    C = min(C, T)
    xf = x.reshape(T, d)

    logits = (xf.astype(jnp.float32) @ params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)
    if cfg.normalize_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    top_p = top_p * cfg.routed_scaling

    me = probs.mean(axis=0)
    ce = jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32).mean(axis=0)
    aux = cfg.aux_loss_weight * E * jnp.sum(me * ce)

    flat_e = top_e.reshape(T * K).astype(jnp.int32)
    flat_p = top_p.reshape(T * K)
    flat_tok = jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32)[:, None], (T, K)
    ).reshape(T * K)
    rank = _ranks_within_expert(flat_e, E)
    local_e = flat_e - e0
    keep = (rank < C) & (local_e >= 0) & (local_e < E_loc)
    slot = jnp.where(keep, local_e * C + rank, E_loc * C)
    tok_buf = jnp.full((E_loc * C + 1,), T, jnp.int32).at[slot].set(
        flat_tok, mode="drop"
    )[: E_loc * C]
    prob_buf = jnp.zeros((E_loc * C + 1,), jnp.float32).at[slot].set(
        flat_p, mode="drop"
    )[: E_loc * C]
    x_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    xe = x_pad[tok_buf].reshape(E_loc, C, d)
    g = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    ye = ye * prob_buf.reshape(E_loc, C, 1).astype(ye.dtype)
    y = (
        jnp.zeros((T + 1, d), ye.dtype)
        .at[tok_buf.reshape(E_loc * C)]
        .add(ye.reshape(E_loc * C, d), mode="drop")[:T]
    )
    return y.reshape(B, S, d), aux


def _moe_apply_local(
    params,
    cfg: MoEConfig,
    x: jnp.ndarray,
    ctx: ShardingCtx = NULL_CTX,
    capacity_factor: float = 0.0,
    include_shared: bool = True,
):
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.top_k
    T = B * S
    cf = capacity_factor or cfg.capacity_factor
    # cf < 0 => dropless (decode path): every expert can hold every token.
    C = T if cf < 0 else int(np.ceil(T * K / E * cf))
    C = min(C, T)
    xf = x.reshape(T, d)

    # ---- router (f32 for stability) ----------------------------------------
    logits = (xf.astype(jnp.float32) @ params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    top_p, top_e = jax.lax.top_k(probs, K)  # (T, K)
    if cfg.normalize_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    top_p = top_p * cfg.routed_scaling

    # ---- aux load-balance loss (Switch eq. 4-6) -----------------------------
    me = probs.mean(axis=0)  # mean router prob / expert
    one_hot_top1 = jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32)
    ce = one_hot_top1.mean(axis=0)  # fraction routed (top-1) / expert
    aux = cfg.aux_loss_weight * E * jnp.sum(me * ce)

    # ---- dispatch ----------------------------------------------------------
    flat_e = top_e.reshape(T * K).astype(jnp.int32)
    flat_p = top_p.reshape(T * K)
    flat_tok = (
        jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None], (T, K))
    ).reshape(T * K)
    rank = _ranks_within_expert(flat_e, E)
    keep = rank < C
    slot = jnp.where(keep, flat_e * C + rank, E * C)  # E*C = dropped
    tok_buf = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(
        flat_tok, mode="drop"
    )[: E * C]
    prob_buf = jnp.zeros((E * C + 1,), jnp.float32).at[slot].set(
        flat_p, mode="drop"
    )[: E * C]

    x_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    xe = x_pad[tok_buf].reshape(E, C, d)
    xe = ctx.constrain(xe, "expert", None, None)

    # ---- expert FFN (E-sharded batched einsum) ------------------------------
    g = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    ye = ctx.constrain(ye, "expert", None, None)
    ye = ye * prob_buf.reshape(E, C, 1).astype(ye.dtype)

    # ---- combine: scatter-add back to token space ---------------------------
    y = (
        jnp.zeros((T + 1, d), ye.dtype)
        .at[tok_buf.reshape(E * C)]
        .add(ye.reshape(E * C, d), mode="drop")[:T]
    )
    y = ctx.constrain(y.reshape(B, S, d), "batch", None, None).reshape(T, d)

    # ---- shared experts ------------------------------------------------------
    if include_shared and "shared" in params:
        from repro.models.layers import mlp_apply

        y = y + mlp_apply(params["shared"], xf)
    return y.reshape(B, S, d), aux
