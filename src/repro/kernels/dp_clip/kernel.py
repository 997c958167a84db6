"""Pallas kernels for DP-SGD per-example clipping (paper Eqs. 10–11 hot loop).

Two passes over the (B, D) per-example flat-gradient matrix:

  1. ``sq_norms``        — per-example Σ g², tiled over D (VMEM-resident
                           (TB, TD) tiles; fp32 accumulation into a (B, 1)
                           column).
  2. ``scale_accumulate``— Σ_b scale_b · g_b, tiled over (B, D); the B grid
                           axis accumulates into the (1, TD) output row.

Tiling: TD = 16k lanes (128-aligned; 8·16k·4 B ≈ 0.5 MB per tile, well under
the ~16 MB v5e VMEM even with double buffering), TB = 8 sublanes.

Per-example vectors travel as 2-D (B, 1) columns and the accumulator as a
(1, D) row: Mosaic accepts a block whose last two dims are (8k, 128k) or
equal to the array's, and a rank-1 (TB,) block is neither — nor is the
(Squeezed, TB) block it becomes when the engine vmaps the call over clients.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_TB = 8
DEFAULT_TD = 16384


def _sq_norm_kernel(x_ref, out_ref):
    d = pl.program_id(1)

    @pl.when(d == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)
    out_ref[...] += jnp.sum(x * x, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("tb", "td", "interpret"))
def sq_norms(x, tb: int = DEFAULT_TB, td: int = DEFAULT_TD, interpret: bool = True):
    """x: (B, D) -> per-example squared l2 norms (B,). B % tb == D % td == 0."""
    B, D = x.shape
    tb, td = min(tb, B), min(td, D)
    assert B % tb == 0 and D % td == 0, (B, tb, D, td)
    grid = (B // tb, D // td)
    return pl.pallas_call(
        _sq_norm_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tb, td), lambda b, d: (b, d))],
        out_specs=pl.BlockSpec((tb, 1), lambda b, d: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.float32),
        interpret=interpret,
    )(x)[:, 0]


def _scale_acc_kernel(x_ref, s_ref, out_ref):
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)          # (TB, TD)
    s = s_ref[...].astype(jnp.float32)          # (TB, 1)
    out_ref[...] += jnp.sum(x * s, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("tb", "td", "interpret"))
def scale_accumulate(x, scales, tb: int = DEFAULT_TB, td: int = DEFAULT_TD,
                     interpret: bool = True):
    """x: (B, D), scales: (B,) -> Σ_b scales_b · x_b  (D,) fp32."""
    B, D = x.shape
    tb, td = min(tb, B), min(td, D)
    assert B % tb == 0 and D % td == 0, (B, tb, D, td)
    grid = (D // td, B // tb)                   # B innermost: accumulation axis
    return pl.pallas_call(
        _scale_acc_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, td), lambda d, b: (b, d)),
            pl.BlockSpec((tb, 1), lambda d, b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, td), lambda d, b: (0, d)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        interpret=interpret,
    )(x, scales.reshape(B, 1))[0]
