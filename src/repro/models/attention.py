"""Attention: GQA + RoPE/M-RoPE + qk-norm + sliding window + KV-cache decode.

Three execution paths:
  * ``full``     — materializes (sq, skv) logits; short sequences / tests.
  * ``chunked``  — lax.map over q-chunks, lax.scan over kv-chunks with online
                   softmax (flash-attention algorithm in pure JAX). This is the
                   path the multi-pod dry-run lowers — (S×S) logits are never
                   materialized, which is what makes 32k prefill fit.
  * ``decode``   — one query token against a (possibly windowed) KV cache.

The Pallas TPU kernels (repro.kernels.flash_attention) implement the chunked
algorithm with explicit VMEM BlockSpecs, forward and backward; they are the
co-train language model's attention (``core.lm``, through
``kernels.dispatch.flash_attention``), whose ref backend is
``blocked_attention`` below. On the CPU they are validated in interpret mode
against it and against repro.kernels.flash_attention.ref.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import ModelConfig
from repro.models.module import ParamSpec
from repro.models import rope as rope_lib
from repro.models.layers import rmsnorm
from repro.sharding.rules import shard_act

NEG_INF = -2.0e38


def n_q_heads(cfg: ModelConfig) -> int:
    """Query head count incl. perf padding (pad_attn_heads_to, DESIGN.md)."""
    return max(cfg.pad_attn_heads_to, cfg.num_heads)


def attention_specs(cfg: ModelConfig, d_model: Optional[int] = None):
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim
    hq = n_q_heads(cfg)
    spec = {
        "wq": ParamSpec((d, hq, hd), ("embed", "heads", "head_dim"), init="fan_in"),
        "wk": ParamSpec((d, cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim"), init="fan_in"),
        "wv": ParamSpec((d, cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim"), init="fan_in"),
        "wo": ParamSpec((hq, hd, d), ("heads", "head_dim", "embed"), init="fan_in"),
    }
    if cfg.qk_norm:
        spec["q_norm"] = ParamSpec((hd,), ("head_dim",), init="ones")
        spec["k_norm"] = ParamSpec((hd,), ("head_dim",), init="ones")
    return spec


def _mask(q_pos, k_pos, window: int):
    """Causal (+ optional sliding window) mask. q_pos (sq,), k_pos (skv,)."""
    m = k_pos[None, :] <= q_pos[:, None]
    if window:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    return m


def _qk_logits(q, k, softcap: float):
    """q: (b, sq, kvh, g, d); k: (b, skv, kvh, d) -> (b, kvh, g, sq, skv)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    return s


def _pv(p, v):
    """p: (b, kvh, g, sq, skv); v: (b, skv, kvh, d) -> (b, sq, kvh, g, d)."""
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)


def _project_qkv(params, x, cfg: ModelConfig, positions, mrope_positions,
                 rope=(None, 1.0)):
    dtype = x.dtype
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
    if cfg.qk_norm:  # qwen3-style per-head RMS norm on q/k
        q = rmsnorm({"scale": params["q_norm"]}, q, cfg.norm_eps)
        k = rmsnorm({"scale": params["k_norm"]}, k, cfg.norm_eps)
    if cfg.mrope_sections and mrope_positions is not None:
        q = rope_lib.apply_mrope(q, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
        k = rope_lib.apply_mrope(k, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
    else:
        freqs, scale = rope
        q = rope_lib.apply_rope(q, positions, cfg.rope_theta, freqs=freqs,
                                scale=scale)
        k = rope_lib.apply_rope(k, positions, cfg.rope_theta, freqs=freqs,
                                scale=scale)
    q = shard_act(q, ("batch", "seq", "heads", "head_dim"))
    k = shard_act(k, ("batch", "seq", "kv_heads", "head_dim"))
    v = shard_act(v, ("batch", "seq", "kv_heads", "head_dim"))
    return q, k, v


def _full_attention(q, k, v, cfg, q_pos, k_pos, window):
    b, sq, hq, hd = q.shape
    kvh = k.shape[2]
    g = hq // kvh
    qg = q.reshape(b, sq, kvh, g, hd)
    s = _qk_logits(qg, k, cfg.attn_logit_softcap) / jnp.sqrt(hd).astype(jnp.float32)
    m = _mask(q_pos, k_pos, window)
    s = jnp.where(m[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = _pv(p, v)
    return o.reshape(b, sq, hq, hd)


def _chunked_attention(q, k, v, cfg, window, q_chunk=512, kv_chunk=1024,
                       dynamic_skip=False):
    """Flash-attention algorithm in pure JAX (online softmax over KV chunks).

    dynamic_skip: causal(+window) KV-chunk skipping via dynamic loop bounds —
    halves attention work, but reverse-mode AD forbids dynamic trip counts,
    so it's enabled only on non-differentiated paths (prefill/serve)."""
    b, sq, hq, hd = q.shape
    skv = k.shape[1]
    kvh = k.shape[2]
    g = hq // kvh
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    assert sq % q_chunk == 0 and skv % kv_chunk == 0, (sq, q_chunk, skv, kv_chunk)
    nq, nkv = sq // q_chunk, skv // kv_chunk
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    qg = q.reshape(b, nq, q_chunk, kvh, g, hd).transpose(1, 0, 2, 3, 4, 5)

    def per_q_chunk(args):
        qi, qc = args                                   # qc: (b, qcs, kvh, g, hd)
        q_pos = qi * q_chunk + jnp.arange(q_chunk)

        def body(ki, carry):
            m, l, acc = carry
            kc = lax.dynamic_slice_in_dim(k, ki * kv_chunk, kv_chunk, axis=1)
            vc = lax.dynamic_slice_in_dim(v, ki * kv_chunk, kv_chunk, axis=1)
            s = _qk_logits(qc, kc, cfg.attn_logit_softcap) * scale
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            msk = _mask(q_pos, k_pos, window)
            s = jnp.where(msk[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc).astype(jnp.float32)
            return (m_new, l_new, acc_new)

        m0 = jnp.full((b, kvh, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, q_chunk, hd), jnp.float32)
        if dynamic_skip:
            # fully-masked KV chunks are never computed (≈2× attention work)
            hi = jnp.minimum(((qi + 1) * q_chunk + kv_chunk - 1) // kv_chunk, nkv)
            if window:
                lo = jnp.maximum(qi * q_chunk - window + 1, 0) // kv_chunk
            else:
                lo = jnp.zeros((), jnp.int32)
            m, l, acc = lax.fori_loop(lo, hi, body, (m0, l0, a0))
        else:
            # differentiable path: static trip count, mask-only causality
            def scan_body(carry, ki):
                return body(ki, carry), None
            (m, l, acc), _ = lax.scan(scan_body, (m0, l0, a0), jnp.arange(nkv))
        o = acc / jnp.maximum(l, 1e-37)[..., None]      # (b, kvh, g, qcs, hd)
        return o.transpose(0, 3, 1, 2, 4).astype(q.dtype)  # (b, qcs, kvh, g, hd)

    outs = lax.map(per_q_chunk, (jnp.arange(nq), qg))    # (nq, b, qcs, kvh, g, hd)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, hq, hd)
    return out


def blocked_attention(q, k, v, window: int, q_block: int):
    """Causal GQA attention of q (n, s, hq, hd) over k, v (n, s, kvh, hd),
    in blocks of ``q_block`` queries, each block under ``jax.checkpoint``
    (its backward recomputes the block's scores instead of keeping the
    (s × s) probabilities). A block of a windowed layer (``window`` > 0)
    reads only the ``q_block + window`` keys that can reach it; a full
    layer's block reads every key under the causal mask."""
    n, s, hq, hd = q.shape
    kvh = k.shape[2]
    Q = min(q_block, s)
    assert s % Q == 0, (s, Q)
    span = s if not window else min(s, Q + window)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    @jax.checkpoint
    def one(i, qb):
        start = 0 if span == s else jnp.clip(i * Q + Q - span, 0, s - span)
        kb = lax.dynamic_slice_in_dim(k, start, span, axis=1)
        vb = lax.dynamic_slice_in_dim(v, start, span, axis=1)
        sc = _qk_logits(qb.reshape(n, Q, kvh, hq // kvh, hd), kb, 0.0) * scale
        m = _mask(i * Q + jnp.arange(Q), start + jnp.arange(span), window)
        p = jax.nn.softmax(jnp.where(m[None, None, None], sc, NEG_INF), -1)
        return _pv(p, vb).reshape(n, Q, hq, hd)

    qs = q.reshape(n, s // Q, Q, hq, hd).swapaxes(0, 1)
    out = lax.map(lambda a: one(*a), (jnp.arange(s // Q), qs))
    return out.swapaxes(0, 1).reshape(n, s, hq, hd)


def attention(params, x, cfg: ModelConfig, *, positions=None, mrope_positions=None,
              window: int = 0, cache=None, cache_index=None, chunked=None,
              return_kv: bool = False, kv_dtype=jnp.bfloat16,
              rope=(None, 1.0)):
    """Returns (output (b, s, d_model), new_cache or None).

    rope: (frequencies or None, cos/sin scale) of the layer's RoPE
    (``transformer.layer_rope``).

    cache: {"k": (b, S, kvh, hd), "v": ...} — serve path writes the new token
    at ``cache_index`` then attends over positions <= cache_index.
    return_kv (prefill): also return the rotated k/v so the caller can build
    the serving cache.
    """
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v = _project_qkv(params, x, cfg, positions, mrope_positions, rope)

    if cache is not None:
        # --- decode: one (or few) new token(s) against the cache -----------
        S = cache["k"].shape[1]
        idx = cache_index if cache_index is not None else S - 1
        new_k = lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
        new_v = lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
        kvh = new_k.shape[2]
        hq = q.shape[2]
        g = hq // kvh
        qg = q.reshape(b, s, kvh, g, hd)
        sc = _qk_logits(qg, new_k, cfg.attn_logit_softcap) / jnp.sqrt(hd).astype(jnp.float32)
        k_pos = jnp.arange(S)
        valid = k_pos <= idx
        if window:
            valid &= k_pos > (idx - window)
        sc = jnp.where(valid[None, None, None, None, :], sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        o = _pv(p, new_v).reshape(b, s, hq, hd)
        out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(o.dtype))
        return out, {"k": new_k, "v": new_v}

    use_chunked = (chunked if chunked is not None
                   else (s > 2048 and not cfg.force_full_attention))
    if use_chunked:
        # prefill/serve (return_kv) is never differentiated -> block skipping
        o = _chunked_attention(q, k, v, cfg, window, dynamic_skip=return_kv)
    else:
        pos = jnp.arange(s)
        o = _full_attention(q, k, v, cfg, pos, pos, window)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(o.dtype))
    if return_kv:
        return out, {"k": k.astype(kv_dtype), "v": v.astype(kv_dtype)}
    return out, None


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16,
                  num_layers: Optional[int] = None):
    """Stacked-over-layers KV cache matching the scan layout of the decoder."""
    L = num_layers if num_layers is not None else cfg.num_layers
    hd = cfg.resolved_head_dim
    shape = (L, batch, max_seq, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
