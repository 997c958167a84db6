"""Kernel backend dispatch + tile-size autotuning — the single entry point
through which the framework reaches its compute kernels.

Selection policy (replaces the old bare ``use_pallas: bool``):

  * ``auto``      — compiled Pallas on TPU, pure-jnp reference elsewhere.
                    The Pallas interpreter is NEVER chosen automatically: it
                    is strictly slower than the jnp oracle it validates.
  * ``pallas``    — compiled Pallas; raises on platforms without Mosaic
                    support rather than silently degrading.
  * ``interpret`` — Pallas interpreter, for explicit kernel debugging only.
  * ``ref``       — the pure-jnp oracle.

Tile sizes are autotuned on first use and cached per
``(kernel, shape, dtype, backend)``; explicit tiles in ``KernelConfig``
bypass the tuner. The cache is process-global — every jit trace after the
first hits it, so tracing inside vmap/scan pays the search exactly once.
The search itself always runs eagerly, in a fresh thread (JAX's trace
state is per thread), even when the first use is inside a jit/vmap/scan
trace, so it times device execution of each candidate and never the staging
of traced ops. A candidate that fails to compile or run is counted in the
``kernels.autotune`` probe; when every candidate fails the search raises,
naming the kernel and shape.

Every dispatch records the backend it resolved to in the
``kernels.backend`` probe (``"<kernel>:<backend>"`` counters, bumped once
per trace), so a run can prove which implementation it traced; a shape the
kernel does not tile counts as the ``ref`` route it takes
(``flash_attention``).

Fused DP-SGD entry points (paper Eqs. 10–11 hot loop): ``dp_clip`` /
``dp_clip_flat`` fuse flatten→norm→scale→accumulate→noise so the (B, D)
per-example gradient matrix is read at most twice (one norm pass, one
scale-accumulate pass with the 1/denom mean folded into the scales) and the
Gaussian noise is a single (D,) draw on the flat output buffer — no
per-leaf noise loop.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.config import KernelConfig
from repro.obs.layers import layer
from repro.obs.probes import Probe
from repro.kernels.dp_clip import kernel as dp_kernel, ops as dp_ops, ref as dp_ref
from repro.kernels.dp_round import (kernel as dpr_kernel, ops as dpr_ops,
                                    ref as dpr_ref)
from repro.kernels.flash_attention import ops as fl_ops
from repro.kernels.l1_distance import kernel as l1_kernel, ops as l1_ops, ref as l1_ref
from repro.models.attention import blocked_attention
from repro.utils.pytree import tree_flatten_concat, tree_unflatten_concat

# Platforms with a Pallas compile path (Mosaic). GPU/Triton is untested in
# this repo, so it is deliberately NOT auto-selected.
_PALLAS_PLATFORMS = ("tpu",)

_BACKENDS = ("auto", "pallas", "interpret", "ref")


def resolve_backend(requested: str = "auto", platform: Optional[str] = None) -> str:
    """Map a requested backend to a concrete one ("pallas"|"interpret"|"ref").

    ``interpret`` is only ever returned when explicitly requested."""
    if requested not in _BACKENDS:
        raise ValueError(f"unknown kernel backend {requested!r}; "
                         f"expected one of {_BACKENDS}")
    platform = platform or jax.default_backend()
    if requested == "auto":
        return "pallas" if platform in _PALLAS_PLATFORMS else "ref"
    if requested == "pallas" and platform not in _PALLAS_PLATFORMS:
        raise ValueError(
            f"backend='pallas' requires one of {_PALLAS_PLATFORMS}, got "
            f"{platform!r}; use backend='interpret' for explicit debugging "
            f"or 'auto'/'ref' for the jnp reference")
    return requested


# ---------------------------------------------------------------------------
# Autotuner — cached per (kernel, shape, dtype, backend)
# ---------------------------------------------------------------------------

_TuneKey = Tuple[str, Tuple[int, ...], str, str]
_TUNE_CACHE: Dict[_TuneKey, Tuple[int, ...]] = {}
# registry-backed probe (see repro.obs): hit/miss tallies plus the search
# cost itself — how many candidate tilings were timed and the wall-clock
# seconds the searches spent, per scope via probe_deltas("kernels.autotune")
_TUNE_STATS = Probe("kernels.autotune", {"hits": 0, "misses": 0,
                                         "candidates_timed": 0,
                                         "candidates_failed": 0,
                                         "search_seconds": 0.0})

_KERNELS = ("dp_clip", "dp_round", "l1_distance", "flash_attention")
# resolved backend per dispatched kernel, counted at trace time
_BACKEND_STATS = Probe("kernels.backend",
                       {f"{k}:{b}": 0 for k in _KERNELS
                        for b in ("pallas", "interpret", "ref")})


def clear_autotune_cache() -> None:
    _TUNE_CACHE.clear()
    _TUNE_STATS.reset()


def autotune_cache_stats() -> Dict[str, int]:
    return dict(_TUNE_STATS, entries=len(_TUNE_CACHE))


def tuned_tiles() -> Dict[str, Dict[Tuple[int, ...], Tuple[int, ...]]]:
    """The tuner's choices so far: kernel -> {shape: tile}."""
    out: Dict[str, Dict[Tuple[int, ...], Tuple[int, ...]]] = {}
    for (name, shape, _, _), tile in _TUNE_CACHE.items():
        out.setdefault(name, {})[shape] = tile
    return out


def _resolve_for(kernel_name: str, cfg: KernelConfig,
                 tiled: bool = True) -> str:
    """The backend ``kernel_name`` takes, counted in ``kernels.backend``;
    "ref" where the kernel does not tile the shape (``tiled`` false)."""
    backend = resolve_backend(cfg.backend)
    if not tiled:
        backend = "ref"
    _BACKEND_STATS[f"{kernel_name}:{backend}"] += 1
    return backend


def autotune(kernel_name: str, shape: Sequence[int], dtype, backend: str,
             candidates: Sequence[Tuple[int, ...]],
             time_fn: Callable[[Tuple[int, ...]], float],
             trials: int = 2) -> Tuple[int, ...]:
    """Pick the fastest candidate tiling for ``kernel_name`` on ``shape``.

    ``time_fn(candidate) -> seconds`` runs one timed call, in a thread of its
    own so that it executes eagerly even when called under a trace. A
    candidate that raises is counted in ``candidates_failed`` and skipped;
    if none survives, this raises with every candidate's error. The winner is memoized per (kernel, shape,
    dtype, backend) so repeated traces (vmap/scan/re-jit) never re-search."""
    key: _TuneKey = (kernel_name, tuple(int(s) for s in shape),
                     jnp.dtype(dtype).name, backend)
    if key in _TUNE_CACHE:
        _TUNE_STATS["hits"] += 1
        return _TUNE_CACHE[key]
    _TUNE_STATS["misses"] += 1
    search_t0 = time.perf_counter()
    errors = []

    def search():
        best, best_t = None, float("inf")
        for cand in candidates:
            try:
                t = min(float(time_fn(cand)) for _ in range(max(1, trials)))
            except Exception as e:  # noqa: BLE001 — reported below
                _TUNE_STATS["candidates_failed"] += 1
                errors.append(f"  {tuple(cand)}: {type(e).__name__}: "
                              f"{str(e).splitlines()[0] if str(e) else ''}")
                continue
            _TUNE_STATS["candidates_timed"] += 1
            if t < best_t:
                best, best_t = tuple(cand), t
        return best

    # a fresh thread has no trace open: there each candidate compiles and
    # runs on the device even when this call sits inside jit/vmap/scan
    with ThreadPoolExecutor(max_workers=1) as pool:
        best = pool.submit(search).result()
    _TUNE_STATS["search_seconds"] += time.perf_counter() - search_t0
    if best is None:
        raise RuntimeError(
            f"autotune: every candidate tiling failed for {kernel_name} at "
            f"shape {key[1]} ({key[2]}, backend={backend}):\n"
            + "\n".join(errors))
    _TUNE_CACHE[key] = best
    return best


def _timed(fn, *args) -> float:
    """Device seconds of one call of ``fn`` (after a warm-up call that
    compiles it). Refuses to time a traced call: that would measure staging."""
    out = fn(*args)
    if any(isinstance(leaf, jax.core.Tracer)
           for leaf in jax.tree_util.tree_leaves(out)):
        raise RuntimeError("autotune candidate was staged into a trace, "
                           "not executed")
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0


def _dp_clip_candidates(B: int, D: int):
    tbs = [tb for tb in (8, 16, 32) if tb <= max(8, B)]
    tds = [td for td in (2048, 8192, 16384) if td <= max(2048, D)]
    return [(tb, td) for tb in tbs for td in tds] or [(8, 2048)]


def _dp_round_candidates(F: int):
    tfs = [tf for tf in (128, 256, 512) if tf <= max(128, F)]
    return [(tf,) for tf in tfs] or [(128,)]


def _l1_candidates(M: int, D: int):
    tms = [tm for tm in (8, 16) if tm <= max(8, M)]
    tds = [td for td in (2048, 8192) if td <= max(2048, D)]
    return [(tm, td) for tm in tms for td in tds] or [(8, 2048)]


def dp_clip_tiles(shape: Tuple[int, int], dtype, cfg: KernelConfig,
                  backend: str) -> Tuple[int, int]:
    if cfg.dp_clip_tile != (0, 0):
        return cfg.dp_clip_tile
    if backend != "pallas" or not cfg.autotune:
        return (dp_kernel.DEFAULT_TB, dp_kernel.DEFAULT_TD)
    B, D = shape

    def time_fn(cand):
        tb, td = cand
        x = jnp.zeros(shape, dtype)
        return _timed(lambda a: dp_ops.clip_accumulate_flat(
            a, 1.0, interpret=False, tb=tb, td=td), x)

    return autotune("dp_clip", shape, dtype, backend,
                    _dp_clip_candidates(B, D), time_fn,
                    trials=cfg.autotune_trials)


def dp_round_tiles(shape: Tuple[int, int, int], dtype, cfg: KernelConfig,
                   backend: str) -> Tuple[int]:
    """shape = (B, F, C) of the fused round."""
    if cfg.dp_round_tile != 0:
        return (cfg.dp_round_tile,)
    if backend != "pallas" or not cfg.autotune:
        return (dpr_kernel.DEFAULT_TF,)
    B, F, C = shape

    def time_fn(cand):
        (tf,) = cand
        params = {"w": jnp.zeros((F, C), dtype), "b": jnp.zeros((C,), dtype)}
        x = jnp.zeros((B, F), dtype)
        y = jnp.zeros((B,), jnp.int32)
        return _timed(lambda p, a, b: dpr_ops.dp_round_linear(
            p, a, b, clip=1.0, interpret=False, tf=tf), params, x, y)

    return autotune("dp_round", shape, dtype, backend,
                    _dp_round_candidates(F), time_fn,
                    trials=cfg.autotune_trials)


def _mix_halo_candidates(m: int):
    """Row-block widths for the halo mix-step arithmetic; (0,) is the
    untiled lowering (today's default) and always a candidate."""
    return [(0,)] + [(tm,) for tm in (8, 16, 32, 64, 128) if tm < m]


def _halo_mix_probe(buf, idx, s, w, tm: int):
    """The halo mix step's per-row arithmetic on a receive buffer, blocked
    in rows of ``tm`` (0 = untiled) — the shape the autotuner times. Row
    arithmetic is row-independent, so every tile width is bit-identical;
    only the lowering changes."""
    m = idx.shape[0]
    t = buf[:m]

    def block(sl):
        acc = s[sl, None] * t[sl]
        for k in range(idx.shape[1]):
            acc = acc + w[sl, k:k + 1] * buf[idx[sl, k]]
        return acc

    if tm <= 0 or tm >= m:
        return block(slice(None))
    return jnp.concatenate([block(slice(i0, min(i0 + tm, m)))
                            for i0 in range(0, m, tm)], axis=0)


def mix_halo_tiles(shape: Tuple[int, int, int, int], dtype,
                   cfg: KernelConfig, backend: str) -> Tuple[int]:
    """shape = (m, H, degree, feat): local rows, halo rows, neighbor slots,
    flattened trailing size of the mixed leaf. Same policy as the other
    dispatchers: explicit tile bypasses, non-pallas/no-autotune takes the
    static default (untiled, i.e. the pre-autotune lowering), otherwise the
    cached search runs once per (shape, dtype, backend)."""
    if cfg.mix_halo_tile != 0:
        return (cfg.mix_halo_tile,)
    if backend != "pallas" or not cfg.autotune:
        return (0,)
    m, H, d, f = shape

    def time_fn(cand):
        (tm,) = cand
        buf = jnp.zeros((m + H, f), dtype)
        idx = jnp.zeros((m, max(d, 1)), jnp.int32)
        s = jnp.ones((m,), dtype)
        w = jnp.zeros((m, max(d, 1)), dtype)
        return _timed(lambda b: _halo_mix_probe(b, idx, s, w, tm), buf)

    return autotune("mix_halo", shape, dtype, backend,
                    _mix_halo_candidates(m), time_fn,
                    trials=cfg.autotune_trials)


def l1_tiles(shape: Tuple[int, int], dtype, cfg: KernelConfig,
             backend: str) -> Tuple[int, int]:
    if cfg.l1_tile != (0, 0):
        return cfg.l1_tile
    if backend != "pallas" or not cfg.autotune:
        return (l1_kernel.DEFAULT_TM, l1_kernel.DEFAULT_TD)
    M, D = shape

    def time_fn(cand):
        tm, td = cand
        x = jnp.zeros(shape, dtype)
        return _timed(lambda a: l1_ops.pairwise_l1(
            a, interpret=False, tm=tm, td=td), x)

    return autotune("l1_distance", shape, dtype, backend,
                    _l1_candidates(M, D), time_fn,
                    trials=cfg.autotune_trials)


# ---------------------------------------------------------------------------
# Dispatched kernel entry points
# ---------------------------------------------------------------------------

def _cfg(kernels: Optional[KernelConfig]) -> KernelConfig:
    return kernels if kernels is not None else KernelConfig()


def clip_accumulate(flat, clip: float, *, denom: float = 1.0,
                    kernels: Optional[KernelConfig] = None):
    """flat: (B, D) per-example grads -> Σ_b clipped(g_b)/denom (D,) fp32.

    Reads (B, D) at most twice on every backend (norm pass +
    scale-accumulate pass with the mean folded into the scales)."""
    cfg = _cfg(kernels)
    backend = _resolve_for("dp_clip", cfg)
    with layer("dp_clip"):
        if backend == "ref":
            return dp_ref.clip_accumulate(flat, clip, denom=denom)
        tb, td = dp_clip_tiles(tuple(flat.shape), flat.dtype, cfg, backend)
        return dp_ops.clip_accumulate_flat(flat, clip, denom=denom,
                                           interpret=(backend == "interpret"),
                                           tb=tb, td=td)


def dp_clip_flat(flat, clip: float, key=None, *, sigma: float = 0.0,
                 denom: float = 1.0, kernels: Optional[KernelConfig] = None):
    """Fused DP-SGD numerator on a flat (B, D) matrix: clipped mean plus the
    Eq. 11 Gaussian drawn once on the (D,) output buffer. The draw is
    identical across backends (same key -> bit-equal noise); sigma > 0
    without a key raises."""
    # a traced σ counts as positive: fail before the clip passes, not after
    if not dp_ref.static_zero_sigma(sigma) and key is None:
        raise ValueError("sigma > 0 requires a PRNG key (privacy guard)")
    out = clip_accumulate(flat, clip, denom=denom, kernels=kernels)
    with layer("dp_noise"):
        return dp_ref.add_flat_noise(out, key, sigma, clip, denom)


def dp_clip(per_example_grads, clip: float, key=None, *, sigma: float = 0.0,
            denom: Optional[float] = None,
            kernels: Optional[KernelConfig] = None):
    """Fused flatten→norm→scale→accumulate→noise over a per-example gradient
    pytree (leading example dim B on every leaf) -> noised mean pytree.

    The (B, D) matrix is materialized once by the flatten and then read at
    most twice; noise is one flat (D,) draw, killing the per-leaf loop."""
    with layer("dp_clip"):
        flat = jax.vmap(tree_flatten_concat)(per_example_grads)  # (B, D)
        if denom is None:
            denom = float(flat.shape[0])
        out = dp_clip_flat(flat, clip, key, sigma=sigma, denom=denom,
                           kernels=kernels)
        template = jax.tree_util.tree_map(lambda g: g[0], per_example_grads)
        return tree_unflatten_concat(out, template)


def dp_round(loss_fn, params, x, y, key=None, *, clip: float,
             sigma: float = 0.0, denom=None,
             kernels: Optional[KernelConfig] = None):
    """Fused local DP round: per-example grad → clip → accumulate → noise in
    one kernel family (linear softmax model; ``loss_fn`` is only used by the
    ref backend, which runs the composed autodiff pipeline verbatim — the
    ref path is therefore bit-identical to not fusing at all). The Pallas
    path uses the closed-form gradient: two matmul passes over the batch
    instead of a B-way per-example gradient stack plus two clip passes."""
    if not dp_ref.static_zero_sigma(sigma) and key is None:
        raise ValueError("sigma > 0 requires a PRNG key (privacy guard)")
    cfg = _cfg(kernels)
    backend = _resolve_for("dp_round", cfg)
    if backend == "ref":
        return dpr_ref.dp_round_reference(loss_fn, params, x, y, key,
                                          clip=clip, sigma=sigma)
    B, F = x.shape
    C = params["b"].shape[0]
    (tf,) = dp_round_tiles((B, F, C), x.dtype, cfg, backend)
    return dpr_ops.dp_round_linear(params, x, y, key, clip=clip, sigma=sigma,
                                   denom=denom,
                                   interpret=(backend == "interpret"), tf=tf)


def pairwise_l1(weights, kernels: Optional[KernelConfig] = None):
    """weights: (M, D) -> (M, M) ℓ1 distances (paper Eq. 3)."""
    cfg = _cfg(kernels)
    backend = _resolve_for("l1_distance", cfg)
    if backend == "ref":
        return l1_ref.pairwise_l1(weights)
    tm, td = l1_tiles(tuple(weights.shape), weights.dtype, cfg, backend)
    return l1_ops.pairwise_l1(weights, interpret=(backend == "interpret"),
                              tm=tm, td=td)


def flash_attention(q, k, v, *, window: int, ref_q_block: int,
                    kernels: Optional[KernelConfig] = None):
    """Causal GQA attention of q (n, s, hq, d) over k, v (n, s, kvh, d),
    each query reading the keys at or before it (the last ``window`` of
    them where ``window`` > 0), differentiable in q, k and v.

    The Pallas flash kernels (``kernels.flash_attention``: forward, dq and
    dk/dv under one custom VJP) where they tile the shape (``ops.tiles``);
    the ref backend, and any shape they do not tile, takes
    ``models.attention.blocked_attention`` in blocks of ``ref_q_block``
    queries."""
    cfg = _cfg(kernels)
    backend = _resolve_for("flash_attention", cfg,
                           fl_ops.tiles(q.shape[1], q.shape[3]))
    if backend == "ref":
        return blocked_attention(q, k, v, window, ref_q_block)
    return fl_ops.flash_attention(q, k, v, window=window,
                                  interpret=(backend == "interpret"))
