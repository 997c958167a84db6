"""The P4 co-train path's language model: a decoder whose layers follow one
period of attention types (``ModelConfig.layer_pattern``: sliding-window
and full layers, each with its own RoPE), with RMSNorm and an MoE feed-
forward of the held experts (``models.moe.expert_share``) in every layer.

Parameters are one flat dict (the layout every P4 model uses: the noise,
Phase 1's distances and the reference read it in sorted key order), each
layer's leaves stacked over the L layers:

    embed (V, d)  head (d, V)  final_norm (d,)
    attn_norm (L, d)  wq (L, d, hq·hd)  wk, wv (L, d, kvh·hd)  wo (L, hq·hd, d)
    moe_norm (L, d)  router (L, d, E)
    w_gate, w_up (L, held, d, f)  w_down (L, held, f, d)

The block, with h̃ = RMSNorm(h):

    h = x + Attn_ℓ(RMSNorm(x))
    y = h + Σ_{e ∈ top-k ∩ held} w_e · W_down,e(silu(W_gate,e h̃) ⊙ W_up,e h̃)

with w = softmax(h̃·W_r) over all E experts, renormalized over the top k.
The depth is scanned one period at a time (one period runs without the
loop), and within a period each run of layers of one type is one scan
(Mellum2's three sliding layers, then its full one); each layer is
checkpointed, so a backward keeps each layer's input and recomputes the
rest.

A client's rows are int32 token sequences of s + 1 tokens: the model
reads the first s, and each position's label is its next token.

``make_lm`` returns ``(specs, apply)``; ``apply.layer_forward(params,
tokens, probe)`` is the forward whose every parametric layer adds its
per-example squared norm to the probe's cotangent (``core.dp``'s tracked
layers and the expert share), the seam of ``dp.dp_layer_clipped_sum``.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import KernelConfig, ModelConfig, MoEConfig, YarnConfig
from repro.core import dp as dp_lib
from repro.kernels import dispatch
from repro.models import moe as moe_lib
from repro.models.module import ParamSpec
from repro.models.rope import apply_rope
from repro.models.transformer import layer_rope, layer_window
from repro.obs import layer

#: queries per attention block of the ref backend
#: (models.attention.blocked_attention)
Q_BLOCK = 256


def lm_config(fields: dict) -> ModelConfig:
    """A ``ModelConfig`` from plain data: its fields by name, ``moe`` and
    ``yarn`` as dicts of theirs, ``layer_pattern`` as a list."""
    fields = dict(fields)
    fields["moe"] = MoEConfig(**fields.get("moe", {}))
    if fields.get("yarn") is not None:
        fields["yarn"] = YarnConfig(**fields["yarn"])
    fields["layer_pattern"] = tuple(fields.get("layer_pattern", ()))
    return ModelConfig(**fields)


def lm_specs(cfg: ModelConfig):
    d, V, L, hd = cfg.d_model, cfg.vocab_size, cfg.num_layers, cfg.resolved_head_dim
    hq, kvh = cfg.num_heads * hd, cfg.num_kv_heads * hd
    E, held, f = cfg.moe.num_experts, cfg.moe.held_count, cfg.d_ff

    def w(*shape, init="fan_in"):
        return ParamSpec(shape, (None,) * len(shape), init=init)
    return {
        "embed": w(V, d, init="normal"), "head": w(d, V),
        "final_norm": w(d, init="ones"),
        "attn_norm": w(L, d, init="ones"), "wq": w(L, d, hq),
        "wk": w(L, d, kvh), "wv": w(L, d, kvh), "wo": w(L, hq, d),
        "moe_norm": w(L, d, init="ones"), "router": w(L, d, E),
        "w_gate": w(L, held, d, f), "w_up": w(L, held, d, f),
        "w_down": w(L, held, f, d),
    }


def make_lm(cfg: ModelConfig, kernels: Optional[KernelConfig] = None):
    """(specs, apply) of the language model ``cfg`` describes. ``apply``
    maps params and tokens (n, s) to logits (n, s, V); attention takes the
    backend ``kernels`` selects (``kernels.dispatch.flash_attention``)."""
    pattern = cfg.layer_pattern or ("sliding",)
    P = len(pattern)
    assert cfg.num_layers % P == 0, (cfg.num_layers, pattern)
    runs = []                       # (first layer, length, type) in a period
    for j, kind in enumerate(pattern):
        if runs and runs[-1][2] == kind:
            runs[-1] = (runs[-1][0], runs[-1][1] + 1, kind)
        else:
            runs.append((j, 1, kind))
    hd, eps, d = cfg.resolved_head_dim, cfg.norm_eps, cfg.d_model

    def dense(a, w, probe):
        if probe is None:
            return jnp.einsum("nsi,io->nso", a, w)
        return dp_lib.tracked_dense(a, w, probe)

    def norm(x, scale, probe):
        x = x.astype(jnp.float32)
        xhat = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                 + eps)
        if probe is None:
            return xhat * scale
        return dp_lib.tracked_scale(xhat, scale, probe)

    @partial(jax.checkpoint, static_argnums=(5,))
    def attend(q, k, v, wo, probe, window):
        """Attention and its output projection under one checkpoint: the
        layer's backward recomputes the attention's f32 output for wo's
        gradient rather than keep it (134 MB a sequence at 8,192 tokens)
        through the expert share's backward."""
        n, s = q.shape[:2]
        o = dispatch.flash_attention(q, k, v, window=window,
                                     ref_q_block=Q_BLOCK, kernels=kernels)
        return dense(o.reshape(n, s, -1), wo, probe)

    def block(x, lp, kind, probe):
        n, s, _ = x.shape
        freqs, scale = layer_rope(cfg, kind)
        window = layer_window(cfg, kind)
        with layer("attention"), jax.named_scope(f"{kind}_attention"):
            h = norm(x, lp["attn_norm"], probe)
            q = dense(h, lp["wq"], probe).reshape(n, s, cfg.num_heads, hd)
            k = dense(h, lp["wk"], probe).reshape(n, s, cfg.num_kv_heads, hd)
            v = dense(h, lp["wv"], probe).reshape(n, s, cfg.num_kv_heads, hd)
            pos = jnp.arange(s)[None, :]
            q = apply_rope(q, pos, cfg.rope_theta, freqs=freqs, scale=scale)
            k = apply_rope(k, pos, cfg.rope_theta, freqs=freqs, scale=scale)
            x = x + attend(q, k, v, lp["wo"], probe, window)
        h = norm(x, lp["moe_norm"], probe)
        with layer("moe_route"):
            route = moe_lib.share_route(
                dense(h, lp["router"], probe).reshape(n * s, -1), cfg.moe)
        y = moe_lib.expert_share(
            h.reshape(n * s, d), route, lp["w_gate"], lp["w_up"],
            lp["w_down"], jnp.zeros((0,), jnp.float32) if probe is None
            else probe)
        return x + y.reshape(n, s, d)

    def forward(params, tokens, probe=None):
        """Logits (n, s, V) of tokens (n, s); with ``probe`` (n,), every
        parametric layer adds its per-example squared norm to the probe's
        cotangent."""
        if probe is None:
            x = jnp.take(params["embed"], tokens, axis=0)
        else:
            x = dp_lib.tracked_embed(params["embed"], tokens, probe)
        stacked = {k: v for k, v in params.items()
                   if k not in ("embed", "head", "final_norm")}
        periods = jax.tree_util.tree_map(
            lambda t: t.reshape((t.shape[0] // P, P) + t.shape[1:]), stacked)

        def period(x, pp):
            # each run of layers of one type is one scan over its layers
            for j, r, kind in runs:
                step = jax.checkpoint(
                    lambda x, lp, kind=kind: (block(x, lp, kind, probe), None))
                lps = jax.tree_util.tree_map(
                    partial(lambda t, j, r: t[j:j + r], j=j, r=r), pp)
                if r == 1:
                    x, _ = step(x, jax.tree_util.tree_map(lambda t: t[0], lps))
                else:
                    x, _ = jax.lax.scan(step, x, lps)
            return x, None

        x = x.astype(jnp.float32)
        if cfg.num_layers == P:         # one period: no loop to carry it
            x, _ = period(x, jax.tree_util.tree_map(lambda t: t[0], periods))
        else:
            x, _ = jax.lax.scan(period, x, periods)
        x = norm(x, params["final_norm"], probe)
        return dense(x, params["head"], probe)

    def apply(params, tokens):
        return forward(params, tokens, None)

    apply.layer_forward = forward
    return lm_specs(cfg), apply
