"""Pallas kernel: pairwise ℓ1 distance between client weight vectors
(paper Eq. 3, Phase-1 grouping).

The distance matrix is symmetric, so the grid enumerates only the
T(T+1)/2 upper-triangle tile pairs (T = M/TM) via a linearized pair index —
half the FLOPs and half the HBM traffic of the rectangular (Mi, Mj) sweep.
Grid (P, Dk): each step loads (TM, TD) row/col tiles and accumulates
|x_i − x_j| partial sums into the (TM, TM) output tile; the D axis is
innermost so the output tile stays VMEM-resident across the reduction.
Lower-triangle tiles are never written — the ops wrapper mirrors the upper
triangle back (``tri + strict_tri.T``). VPU-only (abs/add) — no MXU use,
which is why this beats an einsum-based |a−b| formulation that would
materialize (M, M, D).

The pair → (row, col) table is decoded once in jnp and handed to the grid
as scalar prefetch, so the index maps are plain SMEM loads (index maps run
on the scalar unit). The output is a (T, T, TM, TM) tile array: a (TM, TM)
block of an (M, M) matrix is not (8, 128)-aligned, a whole tile is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TM = 8
DEFAULT_TD = 8192


def tri_decode(p):
    """Linear pair index p -> tile coords (row, col) with row <= col.

    Enumeration: p = col·(col+1)/2 + row over the triangle. The float sqrt
    inverse is followed by an integer correction step so the decode is exact
    despite fp32 rounding (validated in tests up to ~10⁶ pairs)."""
    pf = p.astype(jnp.float32)
    c = jnp.floor((jnp.sqrt(8.0 * pf + 1.0) - 1.0) * 0.5).astype(p.dtype)
    c = jnp.where((c + 1) * (c + 2) // 2 <= p, c + 1, c)
    c = jnp.where(c * (c + 1) // 2 > p, c - 1, c)
    r = p - c * (c + 1) // 2
    return r, c


def _l1_kernel(rows_ref, cols_ref, xi_ref, xj_ref, out_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    xi = xi_ref[...].astype(jnp.float32)        # (TM, TD) rows
    xj = xj_ref[...].astype(jnp.float32)        # (TM, TD) cols
    out_ref[...] += jnp.sum(jnp.abs(xi[:, None, :] - xj[None, :, :]), axis=2)


@functools.partial(jax.jit, static_argnames=("tm", "td", "interpret"))
def pairwise_l1(x, tm: int = DEFAULT_TM, td: int = DEFAULT_TD, interpret: bool = True):
    """x: (M, D) -> (M, M) with only the upper-triangle tiles written
    (mirror with the ops wrapper). M % tm == D % td == 0."""
    M, D = x.shape
    tm, td = min(tm, M), min(td, D)
    assert M % tm == 0 and D % td == 0, (M, tm, D, td)
    T = M // tm
    P = T * (T + 1) // 2
    rows, cols = tri_decode(jnp.arange(P, dtype=jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(P, D // td),                      # D innermost: reduction axis
        in_specs=[
            pl.BlockSpec((tm, td), lambda p, k, r, c: (r[p], k)),
            pl.BlockSpec((tm, td), lambda p, k, r, c: (c[p], k)),
        ],
        out_specs=pl.BlockSpec((None, None, tm, tm),
                               lambda p, k, r, c: (r[p], c[p], 0, 0)),
    )
    tiles = pl.pallas_call(
        _l1_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, T, tm, tm), jnp.float32),
        interpret=interpret,
    )(rows, cols, x, x)
    return tiles.transpose(0, 2, 1, 3).reshape(M, M)
