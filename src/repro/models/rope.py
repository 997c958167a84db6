"""Rotary position embeddings: standard RoPE, YaRN and Qwen2-VL M-RoPE.

YaRN (arXiv:2309.00071), as HF's ``rope_parameters`` define it: each
frequency channel blends the interpolated frequency (θ-frequency / factor)
and the extrapolated one (θ-frequency) by a linear ramp over the channels
between the correction dimensions of ``beta_fast`` and ``beta_slow``
rotations at the original context, and cos and sin are scaled by
``attention_factor``.

M-RoPE (arXiv:2409.12191) splits the head_dim/2 frequency channels into
(temporal, height, width) sections and rotates each section by a different
position stream; text tokens use identical (t,h,w) positions, so M-RoPE
degenerates to RoPE on pure text.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_freqs(head_dim: int, theta: float, yarn):
    """(inverse frequencies (head_dim/2,), cos/sin scale) of YaRN for a
    ``config.YarnConfig``, computed on the host in float32."""
    dim = head_dim

    def correction_dim(rotations):
        return (dim * math.log(yarn.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    pos = theta ** (np.arange(0, dim, 2).astype(np.float32) / dim)
    extrapolated = 1.0 - ramp
    inv = ((1.0 / (yarn.factor * pos)) * (1 - extrapolated)
           + (1.0 / pos) * extrapolated)
    return inv.astype(np.float32), float(yarn.attention_factor)


def apply_rope(x, positions, theta: float = 10000.0, *, freqs=None,
               scale: float = 1.0):
    """x: (..., seq, heads, head_dim); positions: broadcastable (..., seq).
    ``freqs`` (head_dim/2,) replaces θ's frequencies and ``scale``
    multiplies cos and sin (YaRN, ``yarn_freqs``)."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)                          # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]                     # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions_thw, sections, theta: float = 10000.0):
    """M-RoPE. x: (batch, seq, heads, head_dim); positions_thw: (3, batch, seq);
    sections: per-stream channel counts summing to head_dim // 2."""
    hd = x.shape[-1]
    half = hd // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(hd, theta)                               # (half,)
    # Build a per-channel position by selecting the (t|h|w) stream per section.
    angle_parts = []
    off = 0
    for i, sec in enumerate(sections):
        pos = positions_thw[i]                                  # (batch, seq)
        angle_parts.append(pos[..., None].astype(jnp.float32) * freqs[off:off + sec])
        off += sec
    angles = jnp.concatenate(angle_parts, axis=-1)              # (batch, seq, half)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
