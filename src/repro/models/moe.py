"""Mixture-of-Experts layers, with two dispatches.

* GShard capacity (``moe_apply``), for the dry-run presets: every expert is
  held, and tokens beyond an expert's capacity are dropped.
* The dropless expert share (``share_route`` + ``expert_share``), for
  per-example DP on the P4 co-train path: the layer is told which experts
  this chip holds (``MoEConfig.held_first`` / ``held_count``), routes over
  all of them, and computes only its own experts' part of the result,
  every routed token included: assignments are sorted by held expert and
  each held expert runs one grouped matrix product over its rows
  (``jax.lax.ragged_dot``), with no capacity. DP needs it dropless: under
  a capacity, whether one example's token is dropped depends on the other
  examples in the batch, so a layer would mix examples and a per-example
  gradient (and its clipped norm) would not be that example's alone. On
  one chip the share has no exchange; the absent experts' part is left
  out, as on the chip that holds this share.

Capacity dispatch is gather/scatter based (no (tokens × experts × capacity) one-hot
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

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.config import MoEConfig, ModelConfig
from repro.models.module import ParamSpec
from repro.obs.layers import layer
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


# ---------------------------------------------------------------------------
# Dropless expert share
# ---------------------------------------------------------------------------

class ShareRoute(NamedTuple):
    """The held experts' choices of T tokens: each token's top-k choices as
    ``slot``, the index of the chosen expert among the held ones or
    ``held_count`` for an expert held elsewhere, with its ``gate``."""
    slot: jnp.ndarray         # (T, k) int32
    gate: jnp.ndarray         # (T, k) renormalized gate weights


def share_route(logits, moe: MoEConfig) -> ShareRoute:
    """Routing of T tokens from their router logits (T, num_experts):
    softmax over every expert, and the top ``experts_per_token``
    renormalized to sum 1 (as the capacity dispatch does)."""
    first, count = moe.held_first, moe.held_count
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, ids = jax.lax.top_k(probs, moe.experts_per_token)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    local = ids - first
    slot = jnp.where((local >= 0) & (local < count), local, count)
    return ShareRoute(slot.astype(jnp.int32), gate)


_GROUP_GRAD = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
_BY_TRANSPOSE = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((1,), (2,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])

#: (token, choice) rows the grouped products take at a time
ROW_CHUNK = 8192


def _group_grad(a, g, sizes):
    """Per group of rows, aᵀg: (groups, d_a, d_g)."""
    return jax.lax.ragged_dot_general(a, g, sizes, _GROUP_GRAD)


def _times_transpose(g, w, sizes):
    """Per group e of rows, g·w[e]ᵀ, without a transposed copy of w."""
    return jax.lax.ragged_dot_general(g, w, sizes, _BY_TRANSPOSE)


def _chunk_tokens(T: int, k: int) -> int:
    """Tokens a chunk takes: the most that divide T with at most
    ``ROW_CHUNK`` rows of choices."""
    c = max(1, min(T, ROW_CHUNK // k))
    while T % c:
        c -= 1
    return c


class _Dispatch(NamedTuple):
    order: jnp.ndarray        # (c·k,) choice of each sorted row
    inv: jnp.ndarray          # (c·k,) sorted row of each choice
    sizes: jnp.ndarray        # (held,) rows of each held expert
    held: jnp.ndarray         # (c, k) the choice is a held expert


def _dispatch(slot, count):
    """One chunk's choices (c, k) sorted by held expert (the others last;
    within an expert by token), and the inverse order that takes rows back
    to choices: the permutation and its inverse, gathers both ways."""
    flat = slot.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    inv = jnp.argsort(order)
    sizes = jax.ops.segment_sum(jnp.ones_like(flat), flat, count + 1)[:count]
    return _Dispatch(order, inv, sizes, slot < count)


def _chunk_forward(x, d, w_gate, w_up, w_down, k):
    """The grouped products of one chunk: rows, their activations and
    outputs, in sorted order."""
    with layer("moe_route"):
        a = jnp.take(x, d.order // k, axis=0)                   # (c·k, d)
    with layer("moe_experts"):
        g1 = jax.lax.ragged_dot(a, w_gate, d.sizes)
        g3 = jax.lax.ragged_dot(a, w_up, d.sizes)
        h = jax.nn.silu(g1) * g3
        y = jax.lax.ragged_dot(h, w_down, d.sizes)
    return a, g1, g3, h, y


def _by_choice(rows, d, c, k):
    """Sorted rows (c·k, ...) back in choice order (c, k, ...)."""
    with layer("moe_route"):
        return jnp.take(rows, d.inv, axis=0).reshape((c, k) + rows.shape[1:])


def _share_out(x, route, w_gate, w_up, w_down):
    T, dm = x.shape
    k, count = route.slot.shape[1], w_gate.shape[0]
    c = _chunk_tokens(T, k)

    def one(args):
        xc, slot, gate = args
        d = _dispatch(slot, count)
        y = _chunk_forward(xc, d, w_gate, w_up, w_down, k)[-1]
        with layer("moe_route"):
            g = jnp.where(d.held, gate, 0.0)
            return jnp.einsum("ck,ckd->cd", g, _by_choice(y, d, c, k))
    chunks = (x.reshape(T // c, c, dm), route.slot.reshape(T // c, c, k),
              route.gate.reshape(T // c, c, k))
    return jax.lax.map(one, chunks).reshape(T, dm)


@jax.custom_vjp
def expert_share(x, route: ShareRoute, w_gate, w_up, w_down, probe):
    """The held experts' part of the MoE output for tokens x (T, d):
    Σ over each token's held choices of gate · W_down(silu(W_gate x) ⊙
    W_up x), with ``w_*`` (held_count, ...) and ``route`` from
    ``share_route``. Tokens are taken in chunks of at most ``ROW_CHUNK``
    choices; a chunk's choices are sorted by held expert, each expert's
    rows go through one grouped product, and the outputs come back by the
    inverse permutation (gathers both ways: no scatter). Its backward
    recomputes the products (the forward keeps only x and the route) and,
    where ``probe`` (n,) is not empty, adds each example's squared gradient
    norm of the three expert matrices, over only the rows of that example
    and expert (their gate weights included), to the probe's cotangent;
    the n examples are the tokens' equal, consecutive parts."""
    return _share_out(x, route, w_gate, w_up, w_down)


def _expert_share_fwd(x, route, w_gate, w_up, w_down, probe):
    return (_share_out(x, route, w_gate, w_up, w_down),
            (x, route, w_gate, w_up, w_down, probe))


def _expert_share_bwd(res, g_out):
    x, route, w_gate, w_up, w_down, probe = res
    T, dm = x.shape
    k, count, n = route.slot.shape[1], w_gate.shape[0], probe.shape[0]
    c = _chunk_tokens(T, k)
    segs = n > 1

    def body(acc, args):
        dw, seg = acc
        j, xc, slot, gate, go = args
        d = _dispatch(slot, count)
        a, g1, g3, h, y = _chunk_forward(xc, d, w_gate, w_up, w_down, k)
        with layer("moe_route"):
            g = jnp.where(d.held, gate, 0.0)
            d_gate = jnp.where(d.held, jnp.einsum(
                "cd,ckd->ck", go, _by_choice(y, d, c, k)), 0.0)
            tok = d.order // k
            gy = (jnp.take(g.reshape(-1), d.order)[:, None]
                  * jnp.take(go, tok, axis=0))
        with layer("moe_experts"):
            dh = _times_transpose(gy, w_down, d.sizes)
            sig = jax.nn.sigmoid(g1)
            dg3 = dh * g1 * sig
            dg1 = dh * g3 * sig * (1.0 + g1 * (1.0 - sig))
            da = (_times_transpose(dg1, w_gate, d.sizes)
                  + _times_transpose(dg3, w_up, d.sizes))
            pairs = ((a, dg1), (a, dg3), (h, gy))
            dw = tuple(t + _group_grad(p, q, d.sizes)
                       for t, (p, q) in zip(dw, pairs))
        if segs:
            with layer("per_example_grads"):
                # rows by (expert, example): the sort kept token order
                # within an expert, so examples are consecutive in it
                ex = (j * c + tok) // (T // n)
                row_slot = jnp.take(slot.reshape(-1), d.order)
                key = jnp.where(row_slot < count, row_slot * n + ex, count * n)
                s_sizes = jax.ops.segment_sum(jnp.ones_like(key), key,
                                              count * n + 1)[: count * n]
                seg = tuple(t + _group_grad(p, q, s_sizes)
                            for t, (p, q) in zip(seg, pairs))
        dx = jnp.sum(_by_choice(da, d, c, k), axis=1)
        return (dw, seg), (dx, d_gate)

    def zeros(lead):
        return tuple(jnp.zeros((lead,) + w.shape[1:], jnp.float32)
                     for w in (w_gate, w_up, w_down))
    nc = T // c
    chunks = (jnp.arange(nc), x.reshape(nc, c, dm),
              route.slot.reshape(nc, c, k), route.gate.reshape(nc, c, k),
              g_out.reshape(nc, c, dm))
    (dw, seg), (dx, d_gate) = jax.lax.scan(
        body, (zeros(count), zeros(count * n) if segs else ()), chunks)
    if n == 0:
        sq = jnp.zeros_like(probe)
    else:
        with layer("per_example_grads"):
            parts = seg if segs else dw
            sq = sum(jnp.sum(jnp.square(p), axis=(1, 2)).reshape(count, n)
                     for p in parts).sum(axis=0)
    d_route = ShareRoute(None, d_gate.reshape(T, k))
    return (dx.reshape(T, dm), d_route) + dw + (sq,)


expert_share.defvjp(_expert_share_fwd, _expert_share_bwd)
