"""Pallas flash-attention kernels: causal GQA attention, optionally within a
sliding window, forward and backward (dq; dk and dv).

Layouts: q, o, dq are (n, s, hq·d) and k, v, dk, dv are (n, s, kvh·d), the
(n, s, h, d) arrays with their last two axes merged; the per-query
logsumexp and the backward's D = rowsum(dO ⊙ O) are (n, kvh, g, s) f32,
g = hq / kvh.

One grid cell takes the g query heads that share a KV head against one K/V
block, so K and V are read once per group and never repeated. The forward
and dq kernels read the q block (bq, g·d) once per query block and stack it
head by head into one (g·bq, d) MXU operand; the dk/dv kernel takes the g
heads of its q block one after another against its K/V block. The grid is
(n, kvh, query blocks, steps) for the forward and dq kernels and (n, kvh,
key blocks, steps) for the dk/dv kernel, the step axis innermost so that
the running max, sum and accumulators stay in VMEM scratch across it; no
score tensor reaches HBM.

Block skipping comes from the layer's own shape. A query block visits only
the key blocks it can reach: on a full layer (``window`` 0) blocks 0 to its
diagonal, on a sliding layer the blocks from the one holding its first
reachable key (window − 1 keys before its first query) to its diagonal: 5
of 256 keys at window 1,024 and bq = bk = 256. The step axis is as long as
the longest such run; the index maps clamp the block index at the run's last block, so a
step past it fetches nothing new, and its compute is under ``pl.when``.
The mask is applied only in blocks that the diagonal or the window's edge
crosses. The dk/dv kernel visits, for each key block, the query blocks
that reach it, in the same way.

Precision: every dot of the three kernels (forward q·kᵀ and p·v; dq:
q·kᵀ, dO·vᵀ, ds·k; dk/dv: k·qᵀ, p·dO, v·dOᵀ, ds·q) takes f32 operands at
the platform's default precision, as the einsums of
``models.attention.blocked_attention`` do: compiled for the TPU, each
operand is rounded to bfloat16 for one MXU pass with f32 accumulation; in
interpret mode the dot is XLA's default dot of the platform (f32 on the
CPU). q, k and v are read as given (f32 in HBM for f32 inputs); scores,
softmax statistics, accumulators and D are f32, and o, dq, dk and dv are
written in the inputs' dtype (f32 for f32 inputs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
LANES = 128
#: (bq, bk) of the three kernels, pinned on a TPU v5e at the LM cell's
#: shapes (s 8,192, 32 query and 4 KV heads of 128): 256 × 256, 512 × 256
#: and 512 × 512 came within 2 % of each other over a sequence's forwards
#: and backwards; 128 × 128 and 128 × 256 were 16–64 % slower on the full
#: layer's forward and backward
BLOCK_Q, BLOCK_K = 256, 256
# VMEM the compiled kernels may use, for their (g·bq, bk) f32 score blocks
# and temporaries: at bq = bk = 512 these exceed Mosaic's default scoped
# limit (the tiles were chosen among 128–512 on the chip)
VMEM_LIMIT = 96 * 1024 * 1024


# ----------------------------------------------------------------- reach

def kv_span(i, bq: int, bk: int, window: int, xp=jnp):
    """First and last key block that query block ``i`` reaches."""
    hi = ((i + 1) * bq - 1) // bk
    if not window:
        return 0 * hi, hi
    return xp.maximum(i * bq - window + 1, 0) // bk, hi


def q_span(j, bq: int, bk: int, window: int, nq: int, xp=jnp):
    """First and last query block that reaches key block ``j``."""
    lo = (j * bk) // bq
    if not window:
        return lo, 0 * lo + nq - 1
    return lo, xp.minimum(((j + 1) * bk + window - 2) // bq, nq - 1)


def steps(n_outer: int, span) -> int:
    """The longest run of blocks ``span`` gives over ``n_outer`` blocks."""
    lo, hi = span(np.arange(n_outer), xp=np)
    return int(np.max(hi - lo)) + 1


def _crossed(qi, kj, bq: int, bk: int, window: int):
    """Whether the diagonal or the window's edge crosses block (qi, kj)."""
    cross = (kj + 1) * bk - 1 > qi * bq
    if window:
        cross = cross | (kj * bk <= (qi + 1) * bq - 1 - window)
    return cross


def _visible(qi, kj, bq: int, bk: int, window: int, transposed: bool):
    """The (bq, bk) mask of block (qi, kj), or (bk, bq) ``transposed``: a
    key at or before the query, and within its window, compared as the
    key's offset from the query in the block against the blocks' offset."""
    shape = (bk, bq) if transposed else (bq, bk)
    qa, ka = (1, 0) if transposed else (0, 1)
    rel = (jax.lax.broadcasted_iota(jnp.int32, shape, ka)
           - jax.lax.broadcasted_iota(jnp.int32, shape, qa))
    off = qi * bq - kj * bk
    ok = rel <= off
    if window:
        ok = ok & (rel > off - window)
    return ok


def _masked(s, ok, g: int):
    """Scores (g·b, w) of g heads' stacked rows, each head's under the
    (b, w) mask ``ok``."""
    if g == 1:
        return jnp.where(ok, s, NEG_INF)
    b, w = ok.shape
    return jnp.where(ok[None], s.reshape(g, b, w), NEG_INF).reshape(g * b, w)


# ------------------------------------------------------------ in-kernel

def _mxu(a, b, contract, rounding: bool):
    """a·b contracting ``contract`` = (a's axis, b's axis), f32 out; with
    ``rounding`` each operand rounded to bfloat16 first (one MXU pass)."""
    if rounding:
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _stack_heads(ref, g: int, d: int):
    """(b, g·d) block -> (g·b, d): head j's rows at [j·b, (j + 1)·b)."""
    x = ref[...]
    if g == 1:
        return x
    return jnp.concatenate([x[:, j * d:(j + 1) * d] for j in range(g)], 0)


def _unstack_heads(ref, x, g: int, b: int, d: int):
    """Inverse of ``_stack_heads``, written into the (b, g·d) block."""
    for j in range(g):
        ref[:, j * d:(j + 1) * d] = x[j * b:(j + 1) * b].astype(ref.dtype)


def _wide(x, width: int):
    """A (rows, 128) lane-replicated column, as (rows, width)."""
    if width == x.shape[1]:
        return x
    if width % x.shape[1] == 0:
        return jnp.tile(x, (1, width // x.shape[1]))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], width))


def _columns(ref, g: int, b: int):
    """A (g, b) block of per-query values as a (g·b, 128) column."""
    cols = [jnp.broadcast_to(ref[j, :].reshape(b, 1), (b, LANES))
            for j in range(g)]
    return cols[0] if g == 1 else jnp.concatenate(cols, 0)


def _when_computed(run, cross, body):
    """``body(masked)`` where ``run``: masked only where ``cross``."""
    @pl.when(run & cross)
    def _():
        body(True)

    @pl.when(run & jnp.logical_not(cross))
    def _():
        body(False)


# --------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, q_scr, m_scr, l_scr,
                acc_scr, *, g, bq, bk, d, window, scale, nsteps, rounding):
    qi, t = pl.program_id(2), pl.program_id(3)
    lo, hi = kv_span(qi, bq, bk, window)
    kj = lo + t

    @pl.when(t == 0)
    def _():
        q_scr[...] = _stack_heads(q_ref, g, d).astype(q_scr.dtype)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        s = _mxu(q_scr[...], k_ref[...], (1, 1), rounding) * scale
        if masked:
            s = _masked(s, _visible(qi, kj, bq, bk, window, False), g)
        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - _wide(m_next, bk))
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_next
        acc_scr[...] = (_wide(alpha, d) * acc_scr[...]
                        + _mxu(p, v_ref[...], (1, 0), rounding))

    _when_computed(kj <= hi, _crossed(qi, kj, bq, bk, window), body)

    @pl.when(t == nsteps - 1)
    def _():
        l = l_scr[...]
        _unstack_heads(o_ref, acc_scr[...] / _wide(l, d), g, bq, d)
        lse = (m_scr[...] + jnp.log(l)).T[:1]              # (1, g·bq)
        for j in range(g):
            lse_ref[j:j + 1, :] = lse[:, j * bq:(j + 1) * bq]


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT)


def _query_major_specs(g: int, bq: int, bk: int, d: int, window: int):
    """Blocks of the (n, kvh, query blocks, steps) grid: a q-layout block,
    the step's K/V block (clamped at the query block's last reachable
    one) and a (g, bq) block of per-query values."""
    def kv_index(b, h, i, t):
        lo, hi = kv_span(i, bq, bk, window)
        return b, jnp.minimum(lo + t, hi), h

    return (pl.BlockSpec((None, bq, g * d), lambda b, h, i, t: (b, i, h)),
            pl.BlockSpec((None, bk, d), kv_index),
            pl.BlockSpec((None, None, g, bq),
                         lambda b, h, i, t: (b, h, 0, i)))


@functools.partial(jax.jit, static_argnames=(
    "heads", "window", "block_q", "block_k", "interpret"))
def forward(q, k, v, *, heads, window, block_q, block_k, interpret):
    """o (n, s, hq·d) and the logsumexp (n, kvh, g, s) of q (n, s, hq·d)
    over k, v (n, s, kvh·d); ``heads`` = (hq, kvh)."""
    n, s, _ = q.shape
    hq, kvh = heads
    g, d = hq // kvh, q.shape[2] // hq
    bq, bk = block_q, block_k
    nq = s // bq
    nsteps = steps(nq, functools.partial(kv_span, bq=bq, bk=bk,
                                         window=window))
    rounding = not interpret
    q_spec, kv_spec, vec_spec = _query_major_specs(g, bq, bk, d, window)
    rows = g * bq
    return pl.pallas_call(
        functools.partial(_fwd_kernel, g=g, bq=bq, bk=bk, d=d, window=window,
                          scale=1.0 / float(np.sqrt(d)), nsteps=nsteps,
                          rounding=rounding),
        grid=(n, kvh, nq, nsteps), in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, vec_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((n, kvh, g, s), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.bfloat16 if rounding else q.dtype),
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="flash_fwd")(q, k, v)


# -------------------------------------------------------------- backward

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref,
               q_scr, do_scr, lse_scr, dd_scr, dq_scr, *, g, bq, bk, d,
               window, scale, nsteps, rounding):
    qi, t = pl.program_id(2), pl.program_id(3)
    lo, hi = kv_span(qi, bq, bk, window)
    kj = lo + t

    @pl.when(t == 0)
    def _():
        q_scr[...] = _stack_heads(q_ref, g, d).astype(q_scr.dtype)
        do_scr[...] = _stack_heads(do_ref, g, d).astype(do_scr.dtype)
        lse_scr[...] = _columns(lse_ref, g, bq)
        dd_scr[...] = _columns(dd_ref, g, bq)
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def body(masked):
        s = _mxu(q_scr[...], k_ref[...], (1, 1), rounding) * scale
        if masked:
            s = _masked(s, _visible(qi, kj, bq, bk, window, False), g)
        p = jnp.exp(s - _wide(lse_scr[...], bk))
        dp = _mxu(do_scr[...], v_ref[...], (1, 1), rounding)
        ds = p * (dp - _wide(dd_scr[...], bk))
        dq_scr[...] += _mxu(ds, k_ref[...], (1, 0), rounding)

    _when_computed(kj <= hi, _crossed(qi, kj, bq, bk, window), body)

    @pl.when(t == nsteps - 1)
    def _():
        _unstack_heads(dq_ref, dq_scr[...] * scale, g, bq, d)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, g, bq, bk, d, window, scale, nq, nsteps,
                rounding):
    kj, t = pl.program_id(2), pl.program_id(3)
    lo, hi = q_span(kj, bq, bk, window, nq)
    qi = lo + t

    @pl.when(t == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(masked):
        k, v = k_ref[...], v_ref[...]
        if rounding:
            k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
        ok = _visible(qi, kj, bq, bk, window, True) if masked else None
        dk, dv = dk_scr[...], dv_scr[...]
        for j in range(g):                      # the group's query heads
            q, do = q_ref[:, j * d:(j + 1) * d], do_ref[:, j * d:(j + 1) * d]
            s = _mxu(k, q, (1, 1), rounding) * scale          # (bk, bq)
            if masked:
                s = jnp.where(ok, s, NEG_INF)
            p = jnp.exp(s - lse_ref[j:j + 1, :])
            dv += _mxu(p, do, (1, 0), rounding)
            ds = p * (_mxu(v, do, (1, 1), rounding) - dd_ref[j:j + 1, :])
            dk += _mxu(ds, q, (1, 0), rounding)
        dk_scr[...], dv_scr[...] = dk, dv

    _when_computed(qi <= hi, _crossed(qi, kj, bq, bk, window), body)

    @pl.when(t == nsteps - 1)
    def _():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "heads", "window", "block_q", "block_k", "interpret"))
def backward(q, k, v, do, lse, dd, *, heads, window, block_q, block_k,
             interpret):
    """dq, dk, dv of the forward's layouts, from its residuals q, k, v, the
    logsumexp and D = rowsum(dO ⊙ O) (both (n, kvh, g, s)) and dO."""
    n, s, _ = q.shape
    hq, kvh = heads
    g, d = hq // kvh, q.shape[2] // hq
    bq, bk = block_q, block_k
    nq, nk = s // bq, s // bk
    scale = 1.0 / float(np.sqrt(d))
    rounding = not interpret
    rows = g * bq
    op = jnp.bfloat16 if rounding else q.dtype

    q_spec, kv_spec, vec_spec = _query_major_specs(g, bq, bk, d, window)
    dq_steps = steps(nq, functools.partial(kv_span, bq=bq, bk=bk,
                                           window=window))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, g=g, bq=bq, bk=bk, d=d, window=window,
                          scale=scale, nsteps=dq_steps, rounding=rounding),
        grid=(n, kvh, nq, dq_steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, vec_spec, vec_spec],
        out_specs=q_spec, out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((rows, d), op), pltpu.VMEM((rows, d), op),
                        pltpu.VMEM((rows, LANES), jnp.float32),
                        pltpu.VMEM((rows, LANES), jnp.float32),
                        pltpu.VMEM((rows, d), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="flash_dq")(q, k, v, do, lse, dd)

    def qb(j, t):
        lo, hi = q_span(j, bq, bk, window, nq)
        return jnp.minimum(lo + t, hi)

    q_by_kv = pl.BlockSpec((None, bq, g * d),
                           lambda b, h, j, t: (b, qb(j, t), h))
    vec_by_kv = pl.BlockSpec((None, None, g, bq),
                             lambda b, h, j, t: (b, h, 0, qb(j, t)))
    own_kv = pl.BlockSpec((None, bk, d), lambda b, h, j, t: (b, j, h))
    dkv_steps = steps(nk, functools.partial(q_span, bq=bq, bk=bk,
                                            window=window, nq=nq))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, g=g, bq=bq, bk=bk, d=d, window=window,
                          scale=scale, nq=nq, nsteps=dkv_steps,
                          rounding=rounding),
        grid=(n, kvh, nk, dkv_steps),
        in_specs=[q_by_kv, own_kv, own_kv, q_by_kv, vec_by_kv, vec_by_kv],
        out_specs=[own_kv, own_kv],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="flash_dkv")(q, k, v, do, lse, dd)
    return dq, dk, dv
