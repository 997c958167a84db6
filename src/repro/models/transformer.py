"""Decoder assembly for every assigned architecture family.

Families (ModelConfig.family):
  dense   — GQA transformer (qwen3, granite, llama3.2, + vlm/audio backbones)
  moe     — dense blocks with MoE FFN (mixtral, llama4, moonshot)
  hybrid  — zamba2: Mamba2 backbone + ONE shared attention block applied at
            the start of every unit of ``hybrid_attn_every`` mamba layers
  ssm     — xLSTM: units cycling ``xlstm_pattern`` (mLSTM / sLSTM blocks)
  vlm     — dense + vision-embedding merge + M-RoPE (frontend stubbed)
  audio   — dense over frame embeddings, K-codebook output heads

Layers are stacked and iterated with ``lax.scan`` so HLO size is O(1) in
depth (80-layer archs lower in seconds); ``cfg.remat == "block"`` wraps the
scan body in ``jax.checkpoint``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import ModelConfig
from repro.models import mamba2, moe as moe_lib, xlstm as xlstm_lib
from repro.models.attention import attention, attention_specs
from repro.models.layers import (embed, embedding_specs, rmsnorm, rmsnorm_specs,
                                 unembed_specs)
from repro.models.mlp import swiglu, swiglu_specs
from repro.models.module import ParamSpec, stack_specs
from repro.sharding.rules import shard_act


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def _attn_block_specs(cfg: ModelConfig):
    return {"ln": rmsnorm_specs(cfg.d_model), "attn": attention_specs(cfg)}


def _ffn_block_specs(cfg: ModelConfig):
    if cfg.moe.num_experts:
        return {"ln": rmsnorm_specs(cfg.d_model), "ffn": moe_lib.moe_specs(cfg)}
    return {"ln": rmsnorm_specs(cfg.d_model), "ffn": swiglu_specs(cfg.d_model, cfg.d_ff)}


def _dense_block_specs(cfg: ModelConfig):
    a, f = _attn_block_specs(cfg), _ffn_block_specs(cfg)
    return {"attn_ln": a["ln"], "attn_attn": a["attn"],
            "ffn_ln": f["ln"], "ffn": f["ffn"]}


def hybrid_layout(cfg: ModelConfig) -> Tuple[int, int]:
    """(num_units, mamba layers per unit) for zamba2-style stacks."""
    k = max(cfg.hybrid_attn_every, 1)
    assert cfg.num_layers % k == 0, (cfg.num_layers, k)
    return cfg.num_layers // k, k


def xlstm_layout(cfg: ModelConfig) -> Tuple[int, Tuple[str, ...]]:
    pat = cfg.xlstm_pattern or ("m",)
    assert cfg.num_layers % len(pat) == 0, (cfg.num_layers, pat)
    return cfg.num_layers // len(pat), pat


def model_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d, V = cfg.d_model, cfg.vocab_size
    specs: Dict[str, Any] = {"final_norm": rmsnorm_specs(d)}
    if cfg.family != "audio":
        specs["embed"] = embedding_specs(V, d)
    if cfg.family == "audio":
        K = cfg.audio_codebooks
        specs["unembed"] = {"kernel": ParamSpec((K, d, V), ("codebooks", "embed", "vocab"),
                                                init="fan_in")}
    elif not cfg.tie_embeddings:
        specs["unembed"] = unembed_specs(V, d)

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        specs["blocks"] = stack_specs(_dense_block_specs(cfg), cfg.num_layers)
    elif cfg.family == "hybrid":
        units, per = hybrid_layout(cfg)
        mamba_block = {"ln": rmsnorm_specs(d), "mixer": mamba2.mamba2_specs(cfg)}
        specs["shared_attn"] = _attn_block_specs(cfg)
        specs["mamba"] = stack_specs(stack_specs(mamba_block, per, "inner"), units, "units")
    elif cfg.family == "ssm":
        units, pat = xlstm_layout(cfg)
        blocks = {}
        for i, kind in enumerate(pat):
            bs = (xlstm_lib.mlstm_specs(cfg) if kind == "m" else xlstm_lib.slstm_specs(cfg))
            blocks[f"b{i}_{kind}"] = stack_specs(
                {"ln": rmsnorm_specs(d), "mixer": bs,
                 "ffn_ln": rmsnorm_specs(d),
                 "ffn": swiglu_specs(d, 4 * d)},
                units, "units")
        specs["units"] = blocks
    else:
        raise ValueError(cfg.family)
    return specs


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _maybe_remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn) if cfg.remat == "block" else fn


def layer_rope(cfg: ModelConfig, kind: str):
    """(frequencies, cos/sin scale) of a layer type's RoPE: YaRN on "full"
    layers where ``cfg.yarn`` is set, else θ's own (None, 1.0)."""
    if kind == "full" and cfg.yarn is not None:
        from repro.models.rope import yarn_freqs
        freqs, scale = yarn_freqs(cfg.resolved_head_dim, cfg.rope_theta,
                                  cfg.yarn)
        return jnp.asarray(freqs), scale
    return None, 1.0


def layer_window(cfg: ModelConfig, kind: str) -> int:
    """A layer type's attention window: ``cfg.window`` on "sliding" layers,
    0 (every earlier position) on "full" ones."""
    return cfg.window if kind == "sliding" else 0


def _dense_stack(params, x, cfg: ModelConfig, *, positions, mrope_positions,
                 caches=None, cache_index=None, return_kv=False):
    """Scan over stacked dense/moe blocks, one period of ``cfg.layer_pattern``
    per scan step. Returns (x, aux, new_caches)."""
    pattern = cfg.layer_pattern or ("sliding",)
    P = len(pattern)

    def one_layer(x, aux, layer_params, cache_l, kind):
        h, kv = attention(
            layer_params["attn_attn"], rmsnorm(layer_params["attn_ln"], x, cfg.norm_eps),
            cfg, positions=positions, mrope_positions=mrope_positions,
            window=layer_window(cfg, kind), cache=cache_l,
            cache_index=cache_index, return_kv=return_kv,
            kv_dtype=jnp.dtype(cfg.kv_cache_dtype),
            rope=layer_rope(cfg, kind))
        x = x + h
        h = rmsnorm(layer_params["ffn_ln"], x, cfg.norm_eps)
        if cfg.moe.num_experts:
            y, a = moe_lib.moe_apply(layer_params["ffn"], h, cfg)
            aux = aux + a
        else:
            y = swiglu(layer_params["ffn"], h)
        x = shard_act(x + y, ("batch", "seq", "embed_act"))
        return x, aux, kv

    def body(carry, inp):
        x, aux = carry
        period_params, period_caches = inp
        if P == 1:
            x, aux, kv = one_layer(x, aux, period_params, period_caches,
                                   pattern[0])
            return (x, aux), kv
        kvs = []
        for j, kind in enumerate(pattern):
            at = functools.partial(lambda t, j: t[j], j=j)
            x, aux, kv = one_layer(
                x, aux, jax.tree_util.tree_map(at, period_params),
                None if period_caches is None
                else jax.tree_util.tree_map(at, period_caches), kind)
            kvs.append(kv)
        return (x, aux), jax.tree_util.tree_map(lambda *t: jnp.stack(t), *kvs)

    def by_period(tree):
        if P == 1 or tree is None:
            return tree
        return jax.tree_util.tree_map(
            lambda t: t.reshape((t.shape[0] // P, P) + t.shape[1:]), tree)

    body = _maybe_remat(body, cfg)
    (x, aux), kvs = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                             (by_period(params["blocks"]), by_period(caches)),
                             unroll=cfg.unroll_layers)
    if P > 1 and kvs is not None:
        kvs = jax.tree_util.tree_map(
            lambda t: t.reshape((t.shape[0] * P,) + t.shape[2:]), kvs)
    return x, aux, kvs


def _hybrid_stack(params, x, cfg: ModelConfig, *, positions, caches=None,
                  cache_index=None, prefill=False):
    """zamba2: scan over units; each unit = shared attention + ``per`` mamba."""
    shared = params["shared_attn"]

    def unit_body(carry, inp):
        x, aux = carry
        unit_params, attn_cache, mamba_cache = inp
        h, kv = attention(shared["attn"], rmsnorm(shared["ln"], x, cfg.norm_eps), cfg,
                          positions=positions, window=cfg.window,
                          cache=attn_cache, cache_index=cache_index,
                          return_kv=prefill, kv_dtype=jnp.dtype(cfg.kv_cache_dtype))
        x = x + h

        def mamba_body(xc, minp):
            mp, mc = minp
            h, new_mc = mamba2.mamba2_apply(mp["mixer"], rmsnorm(mp["ln"], xc, cfg.norm_eps),
                                            cfg, cache=mc, return_state=prefill)
            return xc + h, new_mc

        x, new_mc = lax.scan(mamba_body, x, (unit_params, mamba_cache),
                             unroll=cfg.hybrid_attn_every if cfg.unroll_inner else 1)
        x = shard_act(x, ("batch", "seq", "embed_act"))
        return (x, aux), (kv, new_mc)

    unit_body = _maybe_remat(unit_body, cfg)
    attn_caches, mamba_caches = (caches if caches is not None else (None, None))
    (x, aux), (kvs, mcs) = lax.scan(
        unit_body, (x, jnp.zeros((), jnp.float32)),
        (params["mamba"], attn_caches, mamba_caches),
        unroll=cfg.unroll_layers)
    return x, aux, (kvs, mcs)


def _ssm_stack(params, x, cfg: ModelConfig, *, caches=None, prefill=False):
    """xLSTM: scan over units cycling the block pattern."""
    _, pat = xlstm_layout(cfg)

    def unit_body(carry, inp):
        x, aux = carry
        unit_params, unit_caches = inp
        new_caches = []
        for i, kind in enumerate(pat):
            bp = unit_params[f"b{i}_{kind}"]
            bc = unit_caches[i] if unit_caches is not None else None
            h_in = rmsnorm(bp["ln"], x, cfg.norm_eps)
            if kind == "m":
                h, nc = xlstm_lib.mlstm_apply(bp["mixer"], h_in, cfg, cache=bc,
                                              return_state=prefill)
            else:
                h, nc = xlstm_lib.slstm_apply(bp["mixer"], h_in, cfg, cache=bc,
                                              return_state=prefill)
            x = x + h
            x = x + swiglu(bp["ffn"], rmsnorm(bp["ffn_ln"], x, cfg.norm_eps))
            x = shard_act(x, ("batch", "seq", "embed_act"))
            new_caches.append(nc)
        return (x, aux), tuple(new_caches)

    unit_body = _maybe_remat(unit_body, cfg)
    unit_caches = caches if caches is not None else None
    (x, aux), new_caches = lax.scan(unit_body, (x, jnp.zeros((), jnp.float32)),
                                    (params["units"], unit_caches),
                                    unroll=cfg.unroll_layers)
    return x, aux, new_caches


def _embed_inputs(params, cfg: ModelConfig, batch: Dict[str, Any], dtype):
    """Family-specific input embedding. Returns (x, mrope_positions)."""
    if cfg.family == "audio":
        return batch["frames"].astype(dtype), None
    x = embed(params["embed"], batch["tokens"], dtype)
    mrope = None
    if cfg.family == "vlm":
        ve = batch["vision_embeds"].astype(dtype)
        v = ve.shape[1]
        x = jnp.concatenate([ve, x[:, v:]], axis=1)
        mrope = batch["mrope_positions"]
    return x, mrope


def _unembed(params, cfg: ModelConfig, x):
    """x: (b, s, d) -> logits."""
    if cfg.family == "audio":
        kern = params["unembed"]["kernel"].astype(x.dtype)
        return jnp.einsum("bsd,kdv->bskv", x, kern).astype(cfg.logits_dtype)
    if cfg.tie_embeddings:
        kern = params["embed"]["table"].astype(x.dtype)  # (V, d)
        return jnp.einsum("bsd,vd->bsv", x, kern).astype(cfg.logits_dtype)
    kern = params["unembed"]["kernel"].astype(x.dtype)
    return jnp.einsum("bsd,dv->bsv", x, kern).astype(cfg.logits_dtype)


def forward(params, cfg: ModelConfig, batch: Dict[str, Any], *,
            caches=None, cache_index=None, return_kv: bool = False,
            last_token_only: bool = False):
    """Full forward pass. Returns (logits, aux_loss, new_caches).

    Modes: train/eval (caches=None, return_kv=False), prefill (caches=None,
    return_kv=True — builds decode caches), decode (caches given +
    cache_index)."""
    dtype = jnp.dtype(cfg.dtype)
    x, mrope = _embed_inputs(params, cfg, batch, dtype)
    x = shard_act(x, ("batch", "seq", "embed_act"))
    b, s = x.shape[0], x.shape[1]
    if cache_index is not None:
        positions = jnp.full((b, s), cache_index, jnp.int32) + jnp.arange(s)[None, :]
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    if cfg.family == "vlm" and mrope is None:
        mrope = jnp.broadcast_to(positions[None], (3, b, s)).astype(jnp.int32)

    prefill = return_kv and caches is None
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        x, aux, new_caches = _dense_stack(
            params, x, cfg, positions=positions, mrope_positions=mrope,
            caches=caches, cache_index=cache_index, return_kv=return_kv)
    elif cfg.family == "hybrid":
        x, aux, new_caches = _hybrid_stack(
            params, x, cfg, positions=positions, caches=caches,
            cache_index=cache_index, prefill=prefill)
    else:
        x, aux, new_caches = _ssm_stack(params, x, cfg, caches=caches,
                                        prefill=prefill)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if last_token_only:
        x = x[:, -1:]
    logits = _unembed(params, cfg, x)
    if cfg.family == "audio":
        logits = shard_act(logits, ("batch", "seq", "codebooks", "vocab"))
    else:
        logits = shard_act(logits, ("batch", "seq", "vocab"))
    return logits, aux, new_caches


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_seq: int, dtype=None):
    """Decode-state pytree matching the scan layout of ``forward``."""
    from repro.models.attention import init_kv_cache
    dtype = dtype or jnp.dtype(cfg.kv_cache_dtype)
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        return init_kv_cache(cfg, batch, max_seq, dtype, num_layers=cfg.num_layers)
    if cfg.family == "hybrid":
        units, per = hybrid_layout(cfg)
        attn_c = init_kv_cache(cfg, batch, max_seq, dtype, num_layers=units)
        mamba_c = mamba2.init_mamba_cache(cfg, batch, units * per, jnp.float32)
        mamba_c = jax.tree_util.tree_map(
            lambda t: t.reshape(units, per, *t.shape[1:]), mamba_c)
        return (attn_c, mamba_c)
    if cfg.family == "ssm":
        units, pat = xlstm_layout(cfg)
        return tuple(xlstm_lib.init_xlstm_cache(cfg, batch, units, jnp.float32)[i]
                     for i in range(len(pat)))
    raise ValueError(cfg.family)
