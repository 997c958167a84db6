"""Differential privacy machinery (paper §3.3 Phase 2, Eqs. 10–12).

* ``clip_by_global_norm`` / ``add_noise`` — Eqs. 10–11.
* ``noble_sigma`` — Eq. 12 (Noble et al. 2022 bound, with l = M' = 1 in the
  P2P setting, as the paper sets them).
* ``rdp_epsilon`` / ``calibrate_sigma`` — Rényi-DP accountant for the
  subsampled Gaussian mechanism (Mironov 2017), used by the FedAvg/Scaffold
  baselines exactly as the paper describes (§4.2.1).
* ``dp_gradients`` — per-example (vmap, optionally chunked) or microbatch
  (lax.scan) clipped + noised gradients. Per-example is the paper-faithful
  path; microbatch is the LM-scale realization (DESIGN.md §2). The flat
  clip-scale-accumulate hot loop goes through ``repro.kernels.dispatch``
  (compiled Pallas on TPU, jnp reference on CPU, tile autotuning) as a fused
  pipeline that reads the (B, D) per-example matrix at most twice and draws
  the Eq. 11 noise once on the flat (D,) buffer.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import KernelConfig
from repro.obs.layers import layer
from repro.utils.pytree import (global_norm, param_count, tree_flatten_concat,
                                tree_unflatten_concat)


# ---------------------------------------------------------------------------
# Eq. 10 — clipping
# ---------------------------------------------------------------------------

def clip_by_global_norm(tree, clip: float):
    """g ← g · min(1, C/‖g‖₂) (paper Eq. 10)."""
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    return jax.tree_util.tree_map(lambda g: (g * scale).astype(g.dtype), tree), norm


# ---------------------------------------------------------------------------
# Eq. 11 — noise
# ---------------------------------------------------------------------------

def add_noise(tree, key, sigma: float, clip: float, denom: float):
    """H̃ = mean(g̃) + (2C/denom)·N(0, σ²)  (paper Eq. 11, denom = s·R).

    Per-leaf draws, deliberately: this serves the microbatch LM-scale path,
    where leaves are sharded model-parameter-sized arrays — flattening the
    tree into one (D,) vector would materialize an extra fp32 copy of the
    model and force a cross-shard gather. The per-example path noises on its
    already-flat buffer instead (repro.kernels.dispatch.dp_clip_flat).

    The scale is an explicit f32 product so a traced σ (the engine's runtime
    noise multiplier) rounds identically to a trace-baked constant σ."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    scale = jnp.float32(2.0 * clip / denom) * jnp.asarray(sigma, jnp.float32)
    noised = [
        g + (scale * jax.random.normal(k, g.shape, jnp.float32)).astype(g.dtype)
        for g, k in zip(leaves, keys)
    ]
    return jax.tree_util.tree_unflatten(treedef, noised)


# ---------------------------------------------------------------------------
# Eq. 12 — Noble et al. σ bound (P2P: l = M' = 1)
# ---------------------------------------------------------------------------

def noble_sigma(epsilon: float, delta: float, *, sample_rate: float = 1.0,
                rounds: int = 100, local_steps: int = 1, client_ratio: float = 1.0,
                num_aggregated: int = 1) -> float:
    """σ_g = s·sqrt(l·T·K·log(2Tl/δ)·log(2/δ)) / (ε·sqrt(M'))  (Eq. 12)."""
    s, T, K, l, M = sample_rate, rounds, local_steps, client_ratio, num_aggregated
    return float(s * math.sqrt(l * T * K * math.log(2 * T * l / delta)
                               * math.log(2 / delta)) / (epsilon * math.sqrt(M)))


# ---------------------------------------------------------------------------
# RDP accountant (subsampled Gaussian) — closed form here; the stateful
# multi-segment ledger built on rdp_increment/rdp_to_epsilon lives in
# repro.engine.accounting.PrivacyLedger
# ---------------------------------------------------------------------------

_ORDERS = tuple([1.5, 2, 3, 4, 5, 6, 8, 10, 12, 16, 20, 24, 32, 48, 64, 128])
RDP_ORDERS = _ORDERS


def _rdp_gaussian(sigma: float, alpha: float) -> float:
    return alpha / (2.0 * sigma ** 2)


def _log_comb(n, k):
    return (math.lgamma(n + 1) - math.lgamma(k + 1) - math.lgamma(n - k + 1))


def _rdp_subsampled(q: float, sigma: float, alpha: int) -> float:
    """Mironov et al. computable bound for Poisson-subsampled Gaussian,
    integer α ≥ 2."""
    if q == 1.0:
        return _rdp_gaussian(sigma, alpha)
    if q == 0.0:
        return 0.0
    # log of sum_{k=0}^{alpha} C(alpha,k) (1-q)^{alpha-k} q^k exp(k(k-1)/(2σ²))
    logs = []
    for k in range(alpha + 1):
        log_term = (_log_comb(alpha, k) + (alpha - k) * math.log1p(-q)
                    + k * math.log(q) + (k * (k - 1)) / (2.0 * sigma ** 2))
        logs.append(log_term)
    m = max(logs)
    total = m + math.log(sum(math.exp(l - m) for l in logs))
    return total / (alpha - 1)


def rdp_increment(q: float, sigma: float, alpha: float) -> float:
    """Per-step RDP of the subsampled Gaussian at order ``alpha``.

    Additive across steps and across segments with different q — the unit
    the PrivacyLedger accumulates. Orders unusable under subsampling (the
    computable bound needs integer α ≥ 2 when q < 1) return ``inf`` so they
    drop out of the min without special-casing at the call site."""
    if q >= 1.0:
        return _rdp_gaussian(sigma, alpha)
    if alpha == int(alpha) and alpha >= 2:
        return _rdp_subsampled(q, sigma, int(alpha))
    return math.inf


def rdp_to_epsilon(rdp: float, alpha: float, delta: float) -> float:
    """RDP(α) → (ε, δ)-DP via the Balle et al. / Canonne conversion."""
    if not math.isfinite(rdp):
        return math.inf
    return rdp + math.log1p(-1.0 / alpha) - math.log(delta * alpha) / (alpha - 1)


def rdp_epsilon(sigma: float, q: float, steps: int, delta: float) -> float:
    """(ε, δ)-DP of ``steps`` compositions of the subsampled Gaussian."""
    return min(rdp_to_epsilon(steps * rdp_increment(q, sigma, alpha),
                              alpha, delta)
               for alpha in _ORDERS)


def calibrate_sigma(target_eps: float, delta: float, q: float, steps: int,
                    lo: float = 0.2, hi: float = 200.0) -> float:
    """Binary-search the smallest σ meeting (ε, δ) after ``steps`` rounds."""
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if rdp_epsilon(mid, q, steps, delta) > target_eps:
            lo = mid
        else:
            hi = mid
    return hi


# ---------------------------------------------------------------------------
# DP gradients — per-example (paper-faithful) and microbatch (LM-scale)
# ---------------------------------------------------------------------------

def _per_example_grad_fn(loss_fn: Callable):
    def one(p, ex):
        ex = jax.tree_util.tree_map(lambda t: t[None], ex)
        return jax.grad(loss_fn)(p, ex)
    return one


def dp_gradients(loss_fn: Callable, params, batch, key, *, clip: float,
                 sigma: float, microbatches: int = 0,
                 per_example_chunk: int = 0,
                 kernels: Optional[KernelConfig] = None):
    """Clipped + noised gradient of ``loss_fn(params, batch) -> scalar``.

    microbatches == 0 — exact per-example DP-SGD: vmap the gradient over the
    leading batch axis, then the fused dispatch pipeline (Eqs. 10–11):
    flatten→norm→scale→accumulate→noise, reading the (B, D) per-example
    matrix at most twice and drawing noise once on the flat (D,) buffer.
    ``per_example_chunk = c`` (c must divide B) scans B/c chunks of c
    vmapped examples into a flat (D,) accumulator — identical semantics, but
    peak memory is c× the parameter size instead of B×, so batch size is no
    longer capped by the per-example gradient stack.

    microbatches == k — LM-scale approximation: split the batch into k
    microbatches (lax.scan), clip each microbatch-mean gradient, average,
    noise. Exact per-example grads on a 72B model are memory-infeasible; this
    is the standard large-scale DP realization (DESIGN.md §2).

    ``kernels`` selects the kernel backend (repro.kernels.dispatch); None
    uses the default policy (compiled Pallas on TPU, jnp reference on CPU).
    """
    from repro.kernels import dispatch
    n = jax.tree_util.tree_leaves(batch)[0].shape[0]

    if microbatches == 0:
        one = _per_example_grad_fn(loss_fn)
        c = per_example_chunk
        if c:
            # c must divide B (c == B degenerates to the full vmap below);
            # silently ignoring a bad chunk size would fall back to B× memory
            assert c <= n and n % c == 0, (n, c)
        if c and c < n:
            # chunked-vmap: per-example clipping is independent across
            # examples, so chunk clip-sums add exactly
            from repro.kernels.dp_clip.ref import add_flat_noise
            chunks = jax.tree_util.tree_map(
                lambda t: t.reshape((n // c, c) + t.shape[1:]), batch)

            def body(acc, bchunk):
                with layer("per_example_grads"):
                    per_ex = jax.vmap(one, in_axes=(None, 0))(params, bchunk)
                    flat = jax.vmap(tree_flatten_concat)(per_ex)  # (c, D)
                # denom folded into the per-example scales: chunk sums are
                # already /n, so their total is the mean — no extra (D,) pass
                return acc + dispatch.clip_accumulate(flat, clip,
                                                      denom=float(n),
                                                      kernels=kernels), None

            D = param_count(params)
            mean, _ = jax.lax.scan(body, jnp.zeros((D,), jnp.float32), chunks)
            with layer("dp_noise"):
                out = add_flat_noise(mean, key, sigma, clip, float(n))
            return tree_unflatten_concat(out, params)
        with layer("per_example_grads"):
            per_ex = jax.vmap(one, in_axes=(None, 0))(params, batch)
            flat = jax.vmap(tree_flatten_concat)(per_ex)          # (B, D)
        out = dispatch.dp_clip_flat(flat, clip, key, sigma=sigma,
                                    denom=float(n), kernels=kernels)
        return tree_unflatten_concat(out, params)

    k = microbatches
    assert n % k == 0, (n, k)
    from repro.sharding.rules import shard_act
    mb = jax.tree_util.tree_map(
        lambda t: shard_act(t.reshape((k, n // k) + t.shape[1:]),
                            (None, "batch") + (None,) * (t.ndim - 1)),
        batch)

    def body(acc, mbatch):
        g = jax.grad(loss_fn)(params, mbatch)
        g, _ = clip_by_global_norm(g, clip)
        return jax.tree_util.tree_map(lambda a, b: a + b, acc, g), None

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    summed, _ = jax.lax.scan(body, zeros, mb)
    clipped_mean = jax.tree_util.tree_map(lambda s: s / k, summed)
    return add_noise(clipped_mean, key, sigma, clip, float(k))
