"""Trainable flash attention on the (n, s, h, d) GQA layout: the kernels of
``kernel.py`` under one ``jax.custom_vjp``.

The forward saves o and the per-query logsumexp (f32, (n, kvh, g, s)); the
backward takes D = rowsum(dO ⊙ O) and runs the dq and dk/dv kernels, which
recompute each block's scores from q, k and the logsumexp. Merging the last
two axes of q, k, v into (n, s, h·d) is a reshape; K and V keep their kvh
heads (no repeat to hq).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel


def _blocks(s: int, block_q: Optional[int] = None,
            block_k: Optional[int] = None):
    return (min(block_q or kernel.BLOCK_Q, s),
            min(block_k or kernel.BLOCK_K, s))


def tiles(s: int, d: int) -> bool:
    """Whether the kernels tile sequence length s and head size d at the
    pinned blocks (cut to s where longer): s a multiple of both, d of the
    128 lanes."""
    bq, bk = _blocks(s)
    return not (s % bq or s % bk or d % kernel.LANES)


def _operands(q, k, v):
    """q, k, v as the kernels read them: their last two axes merged."""
    return tuple(t.reshape(t.shape[:2] + (-1,)) for t in (q, k, v))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention(q, k, v, window, block_q, block_k, interpret):
    return _attention_fwd(q, k, v, window, block_q, block_k, interpret)[0]


def _attention_fwd(q, k, v, window, block_q, block_k, interpret):
    qkv = _operands(q, k, v)
    o, lse = kernel.forward(*qkv, heads=(q.shape[2], k.shape[2]),
                            window=window, block_q=block_q, block_k=block_k,
                            interpret=interpret)
    o = o.reshape(q.shape)
    return o, (qkv, o, lse)


def _attention_bwd(window, block_q, block_k, interpret, res, do):
    qkv, o, lse = res
    n, s, hq, d = o.shape
    kvh = qkv[1].shape[2] // d
    k_shape = (n, s, kvh, d)
    dd = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
    dd = dd.transpose(0, 2, 1).reshape(n, kvh, hq // kvh, s)
    dq, dk, dv = kernel.backward(
        *qkv, do.reshape(n, s, -1), lse, dd, heads=(hq, kvh), window=window,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return dq.reshape(o.shape), dk.reshape(k_shape), dv.reshape(k_shape)


_attention.defvjp(_attention_fwd, _attention_bwd)


def flash_attention(q, k, v, *, window: int = 0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None, interpret: bool = True):
    """Causal attention of q (n, s, hq, d) over k, v (n, s, kvh, d), each
    query reading the keys at or before it (the last ``window`` of them
    where ``window`` > 0): (n, s, hq, d) in q's dtype, differentiable in
    q, k and v. Blocks default to the pinned ``kernel.BLOCK_Q`` /
    ``BLOCK_K``; a block longer than s is cut to s."""
    s = q.shape[1]
    bq, bk = _blocks(s, block_q, block_k)
    if s % bq or s % bk:
        raise ValueError(f"blocks ({bq}, {bk}) do not tile s = {s}")
    return _attention(q, k, v, window, bq, bk, interpret)
