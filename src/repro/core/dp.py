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
* ``dp_affine_flat`` — the same clipped + noised gradient for a model
  whose logits are an affine map of its input (z = x·w + b), in closed form
  from the per-example logit gradients: no per-example parameter gradient
  is built. It is returned flat, the layout ``P4Trainer``'s stacked affine
  step updates the proxy in.
* ``dp_ghost_gradients`` — the same for a model that exposes its layers
  (``small_models.LayerSeam``): each example's squared gradient norm as a
  sum of per-layer ghost norms from one batched forward and backward, and
  the clipped mean as one batched backward weighted by the clip scales. No
  per-example parameter gradient is built. The ``dp.path`` probe counts
  which route each trace took.
* ``dp_layer_clipped_sum`` — the same clipped sum for a sequence model
  whose layers take their own per-example norms in the backward pass
  (``tracked_dense``, ``tracked_scale``, ``tracked_embed`` and the expert
  share of ``models.moe``): one forward, one backward with each example's
  logit gradient that leaves every example's squared norm on a probe, and
  the backward with s ⊙ dl. Storing every layer's input and output
  gradient, as ``dp_ghost_gradients`` does, would hold about 4 GB for one
  8,192-token sequence of a four-layer MoE model; here each layer's norm is
  taken while its backward runs, from the layer's per-example gradient.
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
from repro.obs.probes import Probe
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
    the PrivacyLedger accumulates. Subsampling never raises a mechanism's
    Rényi divergence (joint quasi-convexity), so at q < 1 the Gaussian's own
    RDP bounds every order, and the computable subsampled bound (integer
    α ≥ 2) tightens it where it applies: ε never grows as q falls."""
    if q >= 1.0:
        return _rdp_gaussian(sigma, alpha)
    if alpha == int(alpha) and alpha >= 2:
        return min(_rdp_subsampled(q, sigma, int(alpha)),
                   _rdp_gaussian(sigma, alpha))
    return _rdp_gaussian(sigma, alpha)


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

#: Which DP route each trace took: one count per traced call of a route.
DP_PATH = Probe("dp.path", {"affine_closed_form": 0, "per_example": 0,
                            "microbatch": 0, "affine_stacked": 0,
                            "ghost_norms": 0, "layer_norms": 0})


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
        DP_PATH["per_example"] += 1
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

    DP_PATH["microbatch"] += 1
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


def dp_affine_flat(x, dl, key, *, clip: float, sigma: float):
    """``dp_gradients``' per-example result for an affine model z = x·w + b
    (params ``{"w": (F, C), "b": (C,)}``) on the batch ``x`` (B, F), from
    ``dl`` (B, C), each example's loss gradient with respect to its own
    logits; returned flat in the [b, w.ravel()] layout of
    ``tree_flatten_concat``.

    Example i's gradient is (dlᵢ, xᵢ ⊗ dlᵢ), so its squared norm is
    ‖dlᵢ‖²·(1 + ‖xᵢ‖²) and the clipped mean is (Σ sᵢ·dlᵢ, xᵀ(s ⊙ dl)): one
    pass over x for the norms and one f32 contraction, instead of a (B, D)
    per-example stack read twice. The contraction runs at HIGHEST precision,
    as the stack's exact f32 outer products do. The noise is the same
    ``add_flat_noise`` draw on the same flat layout, so the same key gives
    bit-identical noise on both routes."""
    DP_PATH["affine_closed_form"] += 1
    from repro.kernels.dp_clip.ref import add_flat_noise
    n = x.shape[0]
    x32 = x.astype(jnp.float32)
    with layer("per_example_grads"):
        xsq = jnp.sum(x32 * x32, axis=-1)                        # (B,)
    with layer("dp_clip"):
        dl = dl.astype(jnp.float32)
        norms = jnp.sqrt(jnp.sum(dl * dl, axis=-1) * (1.0 + xsq))
        scales = jnp.minimum(1.0, clip / jnp.maximum(norms, 1e-12)) / float(n)
        hi = jax.lax.Precision.HIGHEST
        # both halves as contractions over the batch, as the per-example
        # route's scale-accumulate forms them: XLA orders a plain sum over
        # the batch per program, so a client-sharded run would drift by ulps
        mean = {"b": jnp.einsum("bc,b->c", dl, scales, precision=hi),
                "w": jnp.einsum("bf,bc->fc", x32, dl * scales[:, None],
                                precision=hi)}
    with layer("dp_noise"):
        return add_flat_noise(tree_flatten_concat(mean), key, sigma, clip,
                              float(n))


# ---------------------------------------------------------------------------
# Per-layer ghost norms
# ---------------------------------------------------------------------------

def _conv3x3_sq_norms(a, g):
    """Per-example ‖∇W‖² of a 3x3 SAME, stride-1 convolution (no bias) from
    its input a (n, C, H, W) and output gradient g (n, O, H, W), without
    the (O, C, 3, 3) gradient: ∇W[:, :, k] = Σ_t g_t a_{t+k}ᵀ over output
    positions t, so ‖∇W‖² = Σ_k Σ_{t,t'} (a_{t+k}·a_{t'+k})(g_t·g_{t'}):
    the input's position Gram P, zero-padded by one position, read in nine
    shifted windows against the output gradient's Gram."""
    n, _, H, W = a.shape
    hi = jax.lax.Precision.HIGHEST
    a = a.reshape(n, a.shape[1], H * W).astype(jnp.float32)
    g = g.reshape(n, g.shape[1], H * W).astype(jnp.float32)
    P = jnp.einsum("bct,bcs->bts", a, a, precision=hi).reshape(n, H, W, H, W)
    P = jnp.pad(P, ((0, 0),) + ((1, 1),) * 4)
    G = jnp.einsum("bot,bos->bts", g, g, precision=hi).reshape(n, H, W, H, W)
    return sum(jnp.sum(P[:, i:i + H, j:j + W, i:i + H, j:j + W] * G,
                       axis=(1, 2, 3, 4))
               for i in range(3) for j in range(3))


def _dense_sq_norms(a, g):
    """Per-example ‖∇w‖² + ‖∇b‖² of z = a·w + b: ‖g‖²·(1 + ‖a‖²)."""
    a, g = a.astype(jnp.float32), g.astype(jnp.float32)
    return jnp.sum(g * g, axis=-1) * (1.0 + jnp.sum(a * a, axis=-1))


_GHOST_SQ_NORMS = {"conv3x3": _conv3x3_sq_norms, "dense": _dense_sq_norms}


def ghost_sq_norms(kinds, inputs, grads):
    """Each example's squared gradient norm, (n,): the sum over the layers
    ``kinds`` names of the ghost norm from the layer's input and output
    gradient."""
    return sum(_GHOST_SQ_NORMS[kind](inputs[name], grads[name])
               for name, kind in kinds.items())


def dp_ghost_gradients(seam, params, x, logit_grads: Callable, key, *,
                       clip: float, sigma: float, block: int = 0):
    """``dp_gradients``' per-example result for a model with a layer seam
    (``small_models.LayerSeam``) on the batch ``x``, with the logits it is
    taken at: returns (gradient, logits). ``logit_grads`` maps the logits
    (n, C) to each example's loss gradient with respect to its own logits.

    One batched forward through the seam and one ``jax.vjp`` with those
    gradients give every layer's input and every example's output gradient;
    the per-layer ghost norms (at HIGHEST precision) sum to each example's
    squared norm, in blocks of ``block`` examples where 0 < block < n; and
    the clipped mean Σ sᵢ·gradᵢ is the same ``vjp`` called with s ⊙ dl,
    which is exact because no layer mixes examples. The logits come from
    the forward that is differentiated, so the caller needs no forward of
    its own. The noise is the same ``add_flat_noise`` draw on the same flat
    layout, so the same key gives bit-identical noise on both routes."""
    DP_PATH["ghost_norms"] += 1
    from repro.kernels.dp_clip.ref import add_flat_noise
    n = x.shape[0]
    logits, vjp, inputs = jax.vjp(lambda p, t: seam.forward(p, x, t), params,
                                  seam.zero_taps(n), has_aux=True)
    dl = logit_grads(logits).astype(jnp.float32)
    with layer("per_example_grads"):
        _, grads = vjp(dl)
        if block and block < n:
            assert n % block == 0, (n, block)

            def block_sq(i):
                # sliced in place: a block-major copy of the inputs would
                # relayout the whole batch
                part = jax.tree_util.tree_map(
                    lambda t: jax.lax.dynamic_slice_in_dim(t, i * block,
                                                           block),
                    (inputs, grads))
                return ghost_sq_norms(seam.kinds, *part)
            sq = jax.lax.map(block_sq, jnp.arange(n // block)).reshape(n)
        else:
            sq = ghost_sq_norms(seam.kinds, inputs, grads)
    with layer("dp_clip"):
        scales = jnp.minimum(1.0, clip / jnp.maximum(jnp.sqrt(sq), 1e-12)) / float(n)
        mean, _ = vjp(dl * scales[:, None])
    with layer("dp_noise"):
        out = add_flat_noise(tree_flatten_concat(mean), key, sigma, clip,
                             float(n))
    return tree_unflatten_concat(out, params), logits


# ---------------------------------------------------------------------------
# Per-layer norms of a sequence model, taken in its backward pass
# ---------------------------------------------------------------------------

def seq_dense_sq_norms(a, g):
    """Per-example ‖aᵢᵀgᵢ‖², (n,), of z = a·w on sequences a (n, s, d_in)
    with output gradients g (n, s, d_out): each example's gradient aᵢᵀgᵢ
    (d_in·d_out floats, at the default precision, as the layer's own
    gradient), then its squares. At s = 8,192 tokens this costs
    s·d_in·d_out against the s²·(d_in + d_out) of the ghost form
    ⟨aaᵀ, ggᵀ⟩ for every layer of the language model in ``core/lm.py``."""
    a, g = a.astype(jnp.float32), g.astype(jnp.float32)
    return jnp.sum(jnp.square(jnp.einsum("nsi,nso->nio", a, g)), axis=(1, 2))


def _probe_sq(probe, sq):
    """The probe's cotangent: each example's squared norm ``sq()``, or
    zeros where the probe is empty (a forward that takes no norms)."""
    if probe.shape[0] == 0:
        return jnp.zeros_like(probe)
    with layer("per_example_grads"):
        return sq()


@jax.custom_vjp
def tracked_dense(a, w, probe):
    """a (n, s, d_in) · w (d_in, d_out); its backward adds each example's
    ‖∇w‖² to the cotangent of ``probe`` (n,) (none where the probe has
    length 0)."""
    return jnp.einsum("nsi,io->nso", a, w)


def _tracked_dense_fwd(a, w, probe):
    return jnp.einsum("nsi,io->nso", a, w), (a, w, probe)


def _tracked_dense_bwd(res, g):
    a, w, probe = res
    return (jnp.einsum("nso,io->nsi", g, w), jnp.einsum("nsi,nso->io", a, g),
            _probe_sq(probe, lambda: seq_dense_sq_norms(a, g)))


tracked_dense.defvjp(_tracked_dense_fwd, _tracked_dense_bwd)


@jax.custom_vjp
def tracked_scale(xhat, scale, probe):
    """xhat (n, s, d) ⊙ scale (d,), RMSNorm's learned scale; its backward
    adds each example's ‖Σₜ gₜ ⊙ x̂ₜ‖² to the cotangent of ``probe``."""
    return xhat * scale


def _tracked_scale_fwd(xhat, scale, probe):
    return xhat * scale, (xhat, scale, probe)


def _tracked_scale_bwd(res, g):
    xhat, scale, probe = res
    per_ex = jnp.sum(g * xhat, axis=1)                     # (n, d)
    return (g * scale, jnp.sum(per_ex, axis=0),
            _probe_sq(probe, lambda: jnp.sum(jnp.square(per_ex), axis=-1)))


tracked_scale.defvjp(_tracked_scale_fwd, _tracked_scale_bwd)


@jax.custom_vjp
def tracked_embed(table, tokens, probe):
    """Rows ``tokens`` (n, s) of ``table`` (V, d); its backward adds each
    example's Σᵥ ‖Σ_{t: token t = v} gₜ‖² (the direct form: the example's
    own (V, d) gradient, one example at a time) to the cotangent of
    ``probe``."""
    return jnp.take(table, tokens, axis=0)


def _tracked_embed_fwd(table, tokens, probe):
    return jnp.take(table, tokens, axis=0), (table, tokens, probe)


def _tracked_embed_bwd(res, g):
    table, tokens, probe = res
    V = table.shape[0]
    d_table = jax.ops.segment_sum(g.reshape(-1, g.shape[-1]),
                                  tokens.reshape(-1), V)
    return d_table, None, _probe_sq(probe, lambda: jax.lax.map(
        lambda tg: jnp.sum(jnp.square(jax.ops.segment_sum(tg[1], tg[0], V))),
        (tokens, g)))


tracked_embed.defvjp(_tracked_embed_fwd, _tracked_embed_bwd)


def add_noise_by_leaf(tree, key, sigma, clip: float, denom: float):
    """``add_flat_noise`` on ``tree``'s flat layout (leaves in sorted key
    order, each raveled), added leaf by leaf: the same draw and the same
    values, without a flat copy of the tree."""
    from repro.kernels.dp_clip.ref import static_zero_sigma
    if static_zero_sigma(sigma):
        return tree
    if key is None:
        raise ValueError("sigma > 0 requires a PRNG key (refusing to return "
                         "unnoised gradients from a DP path)")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    z = jax.random.normal(key, (sum(t.size for t in leaves),), jnp.float32)
    scale = jnp.float32(2.0 * clip / denom) * jnp.asarray(sigma, jnp.float32)
    out, off = [], 0
    for t in leaves:
        out.append(t + scale * z[off:off + t.size].reshape(t.shape))
        off += t.size
    return jax.tree_util.tree_unflatten(treedef, out)


def dp_layer_clipped_sum(forward: Callable, params, x, logit_grads: Callable,
                         *, clip: float, denom: float):
    """Σᵢ sᵢ·gradᵢ / ``denom`` over the examples of ``x`` (no noise), with
    sᵢ = min(1, C/‖gradᵢ‖) (Eq. 10), and the logits it is taken at: returns
    (sum, logits). ``forward(params, x, probe)`` is the model with every
    parametric layer built from this module's tracked layers (or one that
    likewise adds its per-example squared norms to the probe's cotangent);
    ``logit_grads`` maps the logits to each example's loss gradient with
    respect to its own logits.

    One forward and one ``jax.vjp``: the backward with dl gives every
    example's squared norm on the probe (its parameter gradients are
    dropped), the backward with s ⊙ dl the clipped sum, exact because no
    layer mixes examples. A caller sums blocks of examples and adds the
    noise once (``kernels.dp_clip.ref.add_flat_noise``)."""
    DP_PATH["layer_norms"] += 1
    n = x.shape[0]
    logits, vjp = jax.vjp(lambda p, z: forward(p, x, z), params,
                          jnp.zeros((n,), jnp.float32))
    dl = logit_grads(logits).astype(jnp.float32)
    with layer("per_example_grads"):
        _, sq = vjp(dl)
    with layer("dp_clip"):
        scales = jnp.minimum(1.0, clip / jnp.maximum(jnp.sqrt(sq), 1e-12)) / denom
        total, _ = vjp(dl * scales.reshape((n,) + (1,) * (dl.ndim - 1)))
    return total, logits
