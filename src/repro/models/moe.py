"""Mixture-of-Experts layer with capacity-based sorted dispatch.

Dispatch is gather/scatter based (no (tokens × experts × capacity) one-hot
tensors): token→expert assignments are ranked per-expert with a stable sort,
tokens beyond each expert's capacity are dropped (standard GShard semantics),
and expert FFNs run as one batched (E, C, d) × (E, d, f) einsum.

Sharding: the expert dim shards over the ``data`` axis when divisible
(expert parallelism — llama4's 128 and moonshot's 64 experts over 16-way
data); otherwise expert-internal dims shard over ``model`` (mixtral's 8
experts, tensor-parallel within each expert). The token gather across the
data axis is the all-to-all the roofline analysis attributes to MoE.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.module import ParamSpec
from repro.sharding.rules import shard_act


def moe_specs(cfg: ModelConfig, d_model=None):
    d = d_model or cfg.d_model
    E, f = cfg.moe.num_experts, cfg.d_ff
    spec = {
        "router": ParamSpec((d, E), ("embed", "experts_router"), init="fan_in"),
        "w_gate": ParamSpec((E, d, f), ("experts", "embed", "ffn"), init="fan_in"),
        "w_in": ParamSpec((E, d, f), ("experts", "embed", "ffn"), init="fan_in"),
        "w_out": ParamSpec((E, f, d), ("experts", "ffn", "embed"), init="fan_in"),
    }
    if cfg.moe.shared_expert:
        spec["shared"] = {
            "w_gate": ParamSpec((d, f), ("embed", "ffn"), init="fan_in"),
            "w_in": ParamSpec((d, f), ("embed", "ffn"), init="fan_in"),
            "w_out": ParamSpec((f, d), ("ffn", "embed"), init="fan_in"),
        }
    return spec


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    E, k = cfg.moe.num_experts, cfg.moe.experts_per_token
    cf = cfg.moe.capacity_factor or 1.25
    cap = int(tokens * k * cf / E)
    return max(8, ((cap + 7) // 8) * 8)  # 8-aligned for TPU lanes


def moe_apply(params, x, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (b, s, d) -> (out, aux_loss).

    dispatch="global" (default): one global sort over all tokens — exact
    GShard capacity semantics, but under batch sharding the index-gather
    forces an all-gather of the full token buffer per layer (the dominant
    collective for MoE archs, see EXPERIMENTS.md §Roofline).

    dispatch="local": tokens are dispatched within their data shard with
    per-shard capacity C/S. When expert weights are NOT expert-parallel
    (mixtral: 8 experts < 16-way data axis, weights sharded over
    d_model/d_ff only), no token ever crosses a shard boundary — the MoE
    layer costs the same collectives as a dense TP layer (§Perf hillclimb 2).
    """
    b, s, d = x.shape
    T = b * s
    C = _capacity(T, cfg)
    xf = x.reshape(T, d)

    if cfg.moe.dispatch == "local":
        out, aux = _moe_local(params, xf, cfg, C)
        if out is not None:
            return out.reshape(b, s, d), aux
    out, aux = _moe_tokens(params, xf, cfg, C)
    return out.reshape(b, s, d), aux


def _moe_local(params, xf, cfg: ModelConfig, C: int):
    """shard_map realization of local dispatch: tokens never leave their data
    shard; expert FFNs stay tensor-parallel over ``model`` with an explicit
    psum; the only data-axis collective left is the (FSDP-style) weight
    gather at region entry."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding.rules import _CTX
    ctx = getattr(_CTX, "val", None)
    if ctx is None:
        return None, None
    mesh, rules = ctx
    data_axes = rules.get("batch") or ("data",)
    data_axes = (data_axes,) if isinstance(data_axes, str) else tuple(data_axes)
    S = 1
    for a in data_axes:
        S *= int(mesh.shape.get(a, 1))
    msz = int(mesh.shape.get("model", 1))
    T, d = xf.shape
    E, f = cfg.moe.num_experts, cfg.d_ff
    if S == 1 or T % S or C % S or f % msz:
        return None, None

    w_specs = {
        "router": P(),                        # (d, E) small — replicate
        "w_gate": P(None, None, "model"),     # ff tensor-parallel
        "w_in": P(None, None, "model"),
        "w_out": P(None, "model", None),
    }
    if cfg.moe.shared_expert:
        w_specs["shared"] = {"w_gate": P(None, "model"), "w_in": P(None, "model"),
                             "w_out": P("model", None)}
    local_params = {k: params[k] for k in w_specs}

    def body(p, x_local):
        out, aux = _moe_tokens_tp(p, x_local, cfg, C // S, model_axis="model")
        return out, jax.lax.pmean(aux, data_axes)

    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(w_specs, P(data_axes, None)),
        out_specs=(P(data_axes, None), P()),
        check_vma=False,
    )(local_params, xf)
    return out, aux


def _moe_tokens_tp(params, xf, cfg: ModelConfig, C: int, model_axis: str):
    """_moe_tokens with the ffn contraction psum made explicit (shard_map)."""
    T, d = xf.shape
    E, k = cfg.moe.num_experts, cfg.moe.experts_per_token
    dtype = xf.dtype
    logits = jnp.einsum("td,de->te", xf, params["router"].astype(dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
    me = jnp.mean(probs, axis=0)
    ce_frac = jnp.mean((jax.nn.one_hot(expert_ids, E, dtype=jnp.float32)).sum(1), axis=0)
    aux = cfg.moe.aux_loss_weight * E * jnp.sum(me * ce_frac)

    flat_e = expert_ids.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_g = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    starts = jnp.searchsorted(se, jnp.arange(E), side="left")
    pos = jnp.arange(T * k) - starts[se]
    keep = pos < C
    slot = se * C + jnp.where(keep, pos, 0)
    slot_tok = jnp.zeros((E * C,), jnp.int32).at[jnp.where(keep, slot, E * C - 1)].max(
        jnp.where(keep, st, 0).astype(jnp.int32), mode="drop")
    slot_used = jnp.zeros((E * C,), jnp.bool_).at[slot].max(keep, mode="drop")

    xs = xf[slot_tok].reshape(E, C, d)
    xs = xs * slot_used.reshape(E, C, 1).astype(dtype)
    g = jnp.einsum("ecd,edf->ecf", xs, params["w_gate"].astype(dtype))
    h = jnp.einsum("ecd,edf->ecf", xs, params["w_in"].astype(dtype))
    ys = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h,
                    params["w_out"].astype(dtype))
    # TP combine in the activation dtype (bf16): halves the psum bytes; the
    # fp32 variant measured +17% memory term for no accuracy win at bf16
    # activations (EXPERIMENTS.md §Perf hillclimb 2, iter 3)
    ys = jax.lax.psum(ys, model_axis)
    ys = ys.reshape(E * C, d)

    out = jnp.zeros((T, d), dtype)
    w = jnp.where(keep, sg, 0.0).astype(dtype)
    out = out.at[st].add(ys[slot] * w[:, None], mode="drop")

    if cfg.moe.shared_expert:
        sh = params["shared"]
        sg_ = jax.nn.silu(jnp.einsum("td,df->tf", xf, sh["w_gate"].astype(dtype)))
        hh = jnp.einsum("td,df->tf", xf, sh["w_in"].astype(dtype))
        shared_out = jnp.einsum("tf,fd->td", sg_ * hh, sh["w_out"].astype(dtype))
        out = out + jax.lax.psum(shared_out.astype(jnp.float32), model_axis).astype(dtype)

    return out, aux


def _moe_tokens(params, xf, cfg: ModelConfig, C: int):
    """Capacity dispatch + expert FFN for flat tokens xf: (T, d)."""
    T, d = xf.shape
    E, k = cfg.moe.num_experts, cfg.moe.experts_per_token
    dtype = xf.dtype

    logits = jnp.einsum("td,de->te", xf, params["router"].astype(dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)             # (T, k)
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    # Load-balance aux loss (Switch/Mixtral form).
    me = jnp.mean(probs, axis=0)                                # mean prob per expert
    ce_frac = jnp.mean(
        (jax.nn.one_hot(expert_ids, E, dtype=jnp.float32)).sum(1), axis=0)  # token frac
    aux = cfg.moe.aux_loss_weight * E * jnp.sum(me * ce_frac)

    # ---- sorted capacity dispatch ------------------------------------------
    flat_e = expert_ids.reshape(-1)                             # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_g = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    # position of each assignment within its expert
    starts = jnp.searchsorted(se, jnp.arange(E), side="left")   # (E,)
    pos = jnp.arange(T * k) - starts[se]
    keep = pos < C
    slot = se * C + jnp.where(keep, pos, 0)
    # token index per (expert, capacity) slot; empty slots -> token 0, weight 0
    slot_tok = jnp.zeros((E * C,), jnp.int32).at[jnp.where(keep, slot, E * C - 1)].max(
        jnp.where(keep, st, 0).astype(jnp.int32), mode="drop")
    slot_used = jnp.zeros((E * C,), jnp.bool_).at[slot].max(keep, mode="drop")

    xs = xf[slot_tok].reshape(E, C, d)                          # gather (all-to-all)
    xs = shard_act(xs, ("experts", "capacity", "embed_act"))
    xs = xs * slot_used.reshape(E, C, 1).astype(dtype)
    g = jnp.einsum("ecd,edf->ecf", xs, params["w_gate"].astype(dtype))
    h = jnp.einsum("ecd,edf->ecf", xs, params["w_in"].astype(dtype))
    ys = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, params["w_out"].astype(dtype))
    ys = ys.reshape(E * C, d)

    # ---- combine ------------------------------------------------------------
    out = jnp.zeros((T, d), dtype)
    w = jnp.where(keep, sg, 0.0).astype(dtype)
    contrib = ys[slot] * w[:, None]
    out = out.at[st].add(contrib, mode="drop")

    if cfg.moe.shared_expert:
        sh = params["shared"]
        sg_ = jax.nn.silu(jnp.einsum("td,df->tf", xf, sh["w_gate"].astype(dtype)))
        hh = jnp.einsum("td,df->tf", xf, sh["w_in"].astype(dtype))
        out = out + jnp.einsum("tf,fd->td", sg_ * hh, sh["w_out"].astype(dtype))

    return out, aux
