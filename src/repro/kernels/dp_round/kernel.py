"""Pallas kernels for the fused DP round on the linear client model.

Two passes over the (B, F) batch, tiled on the feature axis (F is the only
axis that grows with model size; B and C are round-constants):

  1. ``logits_xsq`` — forward matmul x·w accumulated over F tiles, fused
     with the per-example ‖x‖² reduction (the clip-norm factor), so the
     batch is read once for both.
  2. ``wgrad``      — xᵀ·(scaled dlogits): one (tf, C) output tile per F
     tile, no cross-tile accumulation.

Between the passes the host-side op computes softmax−onehot, the factored
per-example clip scales, and the bias gradient — O(B·C) work that stays in
jnp. MXU matmuls accumulate in f32 via ``preferred_element_type``.

The bias enters as a (1, C) row and ‖x‖² leaves as a (B, 1) column: under
the engine's vmap over clients a rank-1 block becomes (Squeezed, n), which
Mosaic refuses unless n spans the whole client axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_TF = 512


def _logits_xsq_kernel(x_ref, w_ref, b_ref, logits_ref, xsq_ref):
    f = pl.program_id(0)

    @pl.when(f == 0)
    def _():
        logits_ref[...] = jnp.broadcast_to(b_ref[...].astype(jnp.float32),
                                           logits_ref.shape)
        xsq_ref[...] = jnp.zeros_like(xsq_ref)

    x = x_ref[...].astype(jnp.float32)              # (B, TF)
    logits_ref[...] += jnp.dot(x, w_ref[...].astype(jnp.float32),
                               preferred_element_type=jnp.float32)
    xsq_ref[...] += jnp.sum(x * x, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("tf", "interpret"))
def logits_xsq(x, w, b, tf: int = DEFAULT_TF, interpret: bool = True):
    """x: (B, F), w: (F, C), b: (C,) -> (logits (B, C) f32, ‖x‖² (B,) f32).
    F % tf == 0 (callers pad)."""
    B, F = x.shape
    C = w.shape[1]
    tf = min(tf, F)
    assert F % tf == 0, (F, tf)
    logits, xsq = pl.pallas_call(
        _logits_xsq_kernel,
        grid=(F // tf,),
        in_specs=[
            pl.BlockSpec((B, tf), lambda f: (0, f)),
            pl.BlockSpec((tf, C), lambda f: (f, 0)),
            pl.BlockSpec((1, C), lambda f: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((B, C), lambda f: (0, 0)),
            pl.BlockSpec((B, 1), lambda f: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, C), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, w, b.reshape(1, C))
    return logits, xsq[:, 0]


def _wgrad_kernel(x_ref, sdl_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)              # (B, TF)
    out_ref[...] = jnp.dot(x.T, sdl_ref[...].astype(jnp.float32),
                           preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tf", "interpret"))
def wgrad(x, sdl, tf: int = DEFAULT_TF, interpret: bool = True):
    """x: (B, F), sdl: (B, C) scaled dlogits -> xᵀ·sdl (F, C) f32."""
    B, F = x.shape
    C = sdl.shape[1]
    tf = min(tf, F)
    assert F % tf == 0, (F, tf)
    return pl.pallas_call(
        _wgrad_kernel,
        grid=(F // tf,),
        in_specs=[
            pl.BlockSpec((B, tf), lambda f: (0, f)),
            pl.BlockSpec((B, C), lambda f: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tf, C), lambda f: (f, 0)),
        out_shape=jax.ShapeDtypeStruct((F, C), jnp.float32),
        interpret=interpret,
    )(x, sdl)
