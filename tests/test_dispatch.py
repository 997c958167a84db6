"""Kernel dispatch layer + fused DP-SGD pipeline (ISSUE 1 tentpole).

Covers: backend resolution policy (interpret never auto-selected), the
autotuner cache, bit-equivalence of the fused dp_clip path vs the pure-jnp
reference under a fixed PRNG key, the chunked-vmap per-example gradient
path, and symmetry/zero-diagonal of the triangular l1 kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import DPConfig, KernelConfig
from repro.core import dp as dp_lib
from repro.kernels import dispatch
from repro.kernels.dp_clip import ref as dp_ref
from repro.kernels.l1_distance import kernel as l1_kernel, ops as l1_ops, ref as l1_ref
from repro.utils.pytree import global_norm, tree_flatten_concat


# ---------------------------------------------------------------------------
# backend resolution policy
# ---------------------------------------------------------------------------

def test_resolve_backend_policy():
    # auto: compiled pallas on TPU, ref elsewhere — NEVER interpret
    assert dispatch.resolve_backend("auto", platform="tpu") == "pallas"
    assert dispatch.resolve_backend("auto", platform="cpu") == "ref"
    assert dispatch.resolve_backend("auto", platform="gpu") == "ref"
    for plat in ("cpu", "tpu", "gpu"):
        assert dispatch.resolve_backend("auto", platform=plat) != "interpret"
    # interpret only when explicitly requested
    assert dispatch.resolve_backend("interpret", platform="cpu") == "interpret"
    assert dispatch.resolve_backend("ref", platform="tpu") == "ref"
    # explicit pallas on an unsupported platform is an error, not a fallback
    with pytest.raises(ValueError):
        dispatch.resolve_backend("pallas", platform="cpu")
    with pytest.raises(ValueError):
        dispatch.resolve_backend("nonsense")


# ---------------------------------------------------------------------------
# autotuner cache
# ---------------------------------------------------------------------------

def test_autotune_cache_hit():
    dispatch.clear_autotune_cache()
    calls = []

    def time_fn(cand):
        calls.append(cand)
        return {(8, 2048): 3.0, (16, 2048): 1.0, (8, 4096): 2.0}[cand]

    cands = [(8, 2048), (16, 2048), (8, 4096)]
    got = dispatch.autotune("dp_clip", (64, 4096), jnp.float32, "pallas",
                            cands, time_fn, trials=1)
    assert got == (16, 2048)                    # fastest candidate wins
    n_first = len(calls)
    assert n_first == len(cands)
    # second call: cache hit, no timing
    again = dispatch.autotune("dp_clip", (64, 4096), jnp.float32, "pallas",
                              cands, time_fn, trials=1)
    assert again == got and len(calls) == n_first
    assert dispatch.autotune_cache_stats()["hits"] == 1
    # different shape/dtype/backend => new search
    dispatch.autotune("dp_clip", (128, 4096), jnp.float32, "pallas",
                      cands, time_fn, trials=1)
    assert len(calls) == 2 * n_first
    assert dispatch.autotune_cache_stats()["entries"] == 2


def test_autotune_skips_failing_candidates():
    dispatch.clear_autotune_cache()

    def time_fn(cand):
        if cand == (8, 2048):
            raise RuntimeError("unsupported tile")
        return 1.0

    got = dispatch.autotune("l1_distance", (8, 8192), jnp.float32, "pallas",
                            [(8, 2048), (16, 2048)], time_fn, trials=1)
    assert got == (16, 2048)
    stats = dispatch.autotune_cache_stats()
    assert stats["candidates_failed"] == 1 and stats["candidates_timed"] == 1


def test_autotune_all_candidates_failing_raises():
    """No silent fallback to candidates[0]: a search in which every tiling
    fails names the kernel and shape, and caches nothing."""
    dispatch.clear_autotune_cache()

    def time_fn(cand):
        raise RuntimeError(f"refused {cand}")

    with pytest.raises(RuntimeError, match=r"l1_distance at shape \(64, 8192\)"):
        dispatch.autotune("l1_distance", (64, 8192), jnp.float32, "pallas",
                          [(8, 2048), (16, 2048)], time_fn, trials=1)
    stats = dispatch.autotune_cache_stats()
    assert stats["candidates_failed"] == 2 and stats["entries"] == 0


def test_autotune_under_jit_records_no_trace_time_timings():
    """A search first reached inside a jit/scan/vmap trace still executes
    each candidate (here a Pallas kernel) eagerly: what is timed is a
    concrete array, never a tracer."""
    dispatch.clear_autotune_cache()
    traced = []

    def time_fn(cand):
        x = jnp.ones((16, 256))

        def run(a):
            out = l1_ops.pairwise_l1(a, tm=cand[0], td=128)
            traced.append(isinstance(out, jax.core.Tracer))
            return out
        return dispatch._timed(run, x)

    def client(y):
        (t,) = dispatch.autotune("probe", (16, 256), jnp.float32, "pallas",
                                 [(8,), (16,)], time_fn, trials=1)
        return y * t

    jax.jit(lambda ys: jax.lax.scan(
        lambda c, y: (c, jax.vmap(client)(y)), 0, ys))(jnp.ones((2, 3)))
    assert traced and not any(traced)
    stats = dispatch.autotune_cache_stats()
    assert stats["candidates_timed"] == 2 and stats["candidates_failed"] == 0
    # and the timer itself refuses a call that was only staged
    with pytest.raises(RuntimeError, match="staged"):
        jax.jit(lambda y: dispatch._timed(lambda a: a + y, jnp.ones(3)))(1.0)


def test_backend_probe_records_resolved_backend(key):
    from repro.obs import probe_deltas
    w = jax.random.normal(key, (8, 256))
    with probe_deltas("kernels.backend") as d:
        dispatch.pairwise_l1(w, kernels=KernelConfig(backend="ref"))
        dispatch.clip_accumulate(w, 1.0, kernels=KernelConfig(
            backend="interpret", dp_clip_tile=(8, 128)))
    got = {k: v for k, v in d["kernels.backend"].items() if v}
    assert got == {"l1_distance:ref": 1, "dp_clip:interpret": 1}


@pytest.mark.parametrize("hd,s,route", [(128, 32, "interpret"),
                                         (32, 32, "ref"), (128, 24, "ref")],
                         ids=["tiled", "narrow-heads", "untiled-length"])
def test_flash_attention_dispatch_counts_the_route_taken(key, monkeypatch,
                                                         hd, s, route):
    """The language model's attention takes the kernels where they tile the
    shape (heads of 128 lanes, s a multiple of the blocks) and
    ``blocked_attention`` otherwise, and the ``kernels.backend`` probe
    counts the route taken; both routes give the same attention."""
    from repro.kernels.flash_attention import kernel as fl_kernel
    from repro.models.attention import blocked_attention
    from repro.obs import probe_deltas
    monkeypatch.setattr(fl_kernel, "BLOCK_Q", 16)
    monkeypatch.setattr(fl_kernel, "BLOCK_K", 16)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, s, h, hd))
               for i, h in enumerate((4, 2, 2)))
    with probe_deltas("kernels.backend") as d:
        got = dispatch.flash_attention(q, k, v, window=5, ref_q_block=8,
                                       kernels=KernelConfig(
                                           backend="interpret"))
    counted = {k: v for k, v in d["kernels.backend"].items() if v}
    assert counted == {f"flash_attention:{route}": 1}
    np.testing.assert_allclose(got, blocked_attention(q, k, v, 5, 8),
                               rtol=2e-5, atol=2e-5)


def test_explicit_tile_override_bypasses_autotune():
    cfg = KernelConfig(dp_clip_tile=(4, 512), l1_tile=(4, 256))
    assert dispatch.dp_clip_tiles((16, 1024), jnp.float32, cfg, "pallas") == (4, 512)
    assert dispatch.l1_tiles((16, 1024), jnp.float32, cfg, "pallas") == (4, 256)


# ---------------------------------------------------------------------------
# fused dp_clip: bit-equivalence vs the jnp reference with a fixed key
# ---------------------------------------------------------------------------

def test_dp_clip_flat_bit_equivalent_to_reference(key):
    """Dispatch policy on CPU: the dispatched fused path IS the jnp
    reference, bit for bit (auto must resolve to ref, never interpret)."""
    B, D = 12, 513
    x = jax.random.normal(key, (B, D)) * 3
    nk = jax.random.fold_in(key, 1)
    got = dispatch.dp_clip_flat(x, 0.7, nk, sigma=1.3, denom=float(B),
                                kernels=KernelConfig(backend="auto"))
    want = dp_ref.dp_clip_reference(x, 0.7, nk, sigma=1.3, denom=float(B))
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_dp_clip_noise_draw_bit_identical_across_backends(key):
    """The Eq. 11 draw goes through one canonical helper, so with the same
    key the noise added by the kernel (interpret) path is bit-identical to
    adding the helper's draw onto the kernel's noiseless output."""
    B, D = 8, 384
    x = jax.random.normal(key, (B, D)) * 2
    nk = jax.random.fold_in(key, 1)
    cfg = KernelConfig(backend="interpret", dp_clip_tile=(4, 128))
    noiseless = dispatch.dp_clip_flat(x, 0.9, denom=float(B), kernels=cfg)
    noised = dispatch.dp_clip_flat(x, 0.9, nk, sigma=1.3, denom=float(B),
                                   kernels=cfg)
    want = dp_ref.add_flat_noise(noiseless, nk, 1.3, 0.9, float(B))
    assert np.array_equal(np.asarray(noised), np.asarray(want))


def test_dp_clip_sigma_without_key_raises(key):
    """sigma > 0 with no PRNG key must not silently skip the privacy noise."""
    x = jax.random.normal(key, (4, 64))
    with pytest.raises(ValueError, match="PRNG key"):
        dispatch.dp_clip_flat(x, 1.0, sigma=0.5)
    tree = {"w": jax.random.normal(key, (4, 3))}
    with pytest.raises(ValueError, match="PRNG key"):
        dispatch.dp_clip(tree, 1.0, sigma=0.5)


def test_per_example_chunk_must_divide_batch(key):
    params = {"w": jax.random.normal(key, (3, 2))}
    batch = {"x": jax.random.normal(key, (10, 3)),
             "y": jax.random.normal(key, (10, 2))}
    with pytest.raises(AssertionError):
        dp_lib.dp_gradients(_quad_loss, params, batch, key, clip=0.3,
                            sigma=0.0, per_example_chunk=4)   # 10 % 4 != 0
    with pytest.raises(AssertionError):
        dp_lib.dp_gradients(_quad_loss, params, batch, key, clip=0.3,
                            sigma=0.0, per_example_chunk=16)  # c > B
    # c == B degenerates cleanly to the full vmap path
    g = dp_lib.dp_gradients(_quad_loss, params, batch, key, clip=0.3,
                            sigma=0.0, per_example_chunk=10)
    assert np.isfinite(np.asarray(g["w"])).all()


def test_dp_clip_tree_matches_unfused_semantics(key):
    """Fused pipeline == per-example clip (Eq. 10) -> mean, without noise."""
    tree = {"w": jax.random.normal(key, (6, 10, 3)) * 5,
            "b": jax.random.normal(jax.random.fold_in(key, 1), (6, 7))}
    clip = 0.5
    got = dispatch.dp_clip(tree, clip)          # no key => no noise
    norms = jax.vmap(global_norm)(tree)
    scale = jnp.minimum(1.0, clip / jnp.maximum(norms, 1e-12))
    want = jax.tree_util.tree_map(
        lambda g: jnp.mean(g * scale.reshape((-1,) + (1,) * (g.ndim - 1)), axis=0),
        tree)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-6)


def test_dp_clip_interpret_backend_matches_ref(key):
    """Explicit interpret backend: kernel output ≈ ref, noise bit-identical."""
    B, D = 8, 384
    x = jax.random.normal(key, (B, D)) * 2
    nk = jax.random.fold_in(key, 2)
    cfg = KernelConfig(backend="interpret", dp_clip_tile=(4, 128))
    got = dispatch.dp_clip_flat(x, 0.9, nk, sigma=0.8, denom=float(B), kernels=cfg)
    want = dp_ref.dp_clip_reference(x, 0.9, nk, sigma=0.8, denom=float(B))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _quad_loss(params, batch):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2)


def test_chunked_per_example_matches_full_vmap(key):
    n = 12
    params = {"w": jax.random.normal(key, (5, 3))}
    x = jax.random.normal(jax.random.fold_in(key, 1), (n, 5)) * 4
    y = jax.random.normal(jax.random.fold_in(key, 2), (n, 3))
    nk = jax.random.fold_in(key, 3)
    for sigma in (0.0, 1.1):
        full = dp_lib.dp_gradients(_quad_loss, params, {"x": x, "y": y}, nk,
                                   clip=0.4, sigma=sigma)
        for c in (3, 4, 6):
            chunked = dp_lib.dp_gradients(_quad_loss, params, {"x": x, "y": y},
                                          nk, clip=0.4, sigma=sigma,
                                          per_example_chunk=c)
            np.testing.assert_allclose(np.asarray(chunked["w"]),
                                       np.asarray(full["w"]),
                                       rtol=1e-5, atol=1e-6)


def test_chunked_path_under_jit(key):
    """The chunked scan + dispatch path must trace under jit (the P4 trainer
    jits the whole local round)."""
    n, c = 8, 4
    params = {"w": jax.random.normal(key, (3, 2))}
    batch = {"x": jax.random.normal(jax.random.fold_in(key, 1), (n, 3)),
             "y": jax.random.normal(jax.random.fold_in(key, 2), (n, 2))}

    @jax.jit
    def f(p, b, k):
        return dp_lib.dp_gradients(_quad_loss, p, b, k, clip=0.3, sigma=0.5,
                                   per_example_chunk=c)

    g = f(params, batch, jax.random.fold_in(key, 3))
    assert np.isfinite(np.asarray(g["w"])).all()


# ---------------------------------------------------------------------------
# triangular l1 kernel
# ---------------------------------------------------------------------------

def test_tri_decode_exact():
    for T in (1, 2, 3, 17, 100):
        P = T * (T + 1) // 2
        r, c = l1_kernel.tri_decode(jnp.arange(P))
        want = [(j, i) for i in range(T) for j in range(i + 1)]
        assert list(zip(np.asarray(r).tolist(), np.asarray(c).tolist())) == want


def test_tri_decode_exact_at_scale():
    """fp32-sqrt decode stays exact out to ~10⁶ pairs (the docstring's
    claimed envelope; fp32 rounding first bites far beyond any real M)."""
    T = 1413                                  # T(T+1)/2 ≈ 1.0e6 pairs
    P = T * (T + 1) // 2
    r, c = l1_kernel.tri_decode(jnp.arange(P))
    r, c = np.asarray(r), np.asarray(c)
    cw = np.repeat(np.arange(T), np.arange(1, T + 1))
    rw = np.arange(P) - cw * (cw + 1) // 2
    assert np.array_equal(c, cw) and np.array_equal(r, rw)


@pytest.mark.parametrize("M,D", [(4, 128), (9, 300), (16, 1024)])
def test_l1_triangular_symmetric_zero_diag(key, M, D):
    w = jax.random.normal(key, (M, D)) * 2
    got = np.asarray(l1_ops.pairwise_l1(w, tm=4, td=128))
    assert np.array_equal(got, got.T)           # exact symmetry (mirror copy)
    assert np.all(np.diag(got) == 0.0)
    np.testing.assert_allclose(got, np.asarray(l1_ref.pairwise_l1(w)),
                               rtol=1e-4, atol=1e-4)


def test_dispatched_pairwise_l1_matches_ref(key):
    w = jax.random.normal(key, (10, 500))
    got = dispatch.pairwise_l1(w)               # auto => ref on CPU
    want = l1_ref.pairwise_l1(w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# fused dp_round: dispatch policy, tiles, and the client_grad fast path
# ---------------------------------------------------------------------------

def _linear_loss():
    from repro.baselines.common import ce_loss, linear_apply
    return ce_loss(linear_apply)


def test_dp_round_candidates_respect_feature_dim():
    assert dispatch._dp_round_candidates(32) == [(128,)]
    assert dispatch._dp_round_candidates(128) == [(128,)]
    assert dispatch._dp_round_candidates(256) == [(128,), (256,)]
    assert dispatch._dp_round_candidates(4096) == [(128,), (256,), (512,)]


def test_dp_round_tiles_policy():
    from repro.kernels.dp_round import kernel as dpr_kernel
    # explicit tile bypasses autotune entirely
    cfg = KernelConfig(dp_round_tile=256)
    assert dispatch.dp_round_tiles((8, 512, 10), jnp.float32, cfg,
                                   "pallas") == (256,)
    # non-pallas backends never autotune: static default
    cfg = KernelConfig()
    assert dispatch.dp_round_tiles((8, 512, 10), jnp.float32, cfg,
                                   "interpret") == (dpr_kernel.DEFAULT_TF,)
    cfg = KernelConfig(autotune=False)
    assert dispatch.dp_round_tiles((8, 512, 10), jnp.float32, cfg,
                                   "pallas") == (dpr_kernel.DEFAULT_TF,)


def test_dp_round_dispatch_bit_equivalent_to_composed_pipeline(key):
    """Dispatch policy on CPU: auto resolves to ref, and the ref backend IS
    dp_gradients — the client_grad fast path cannot move a single bit."""
    B, F, C = 12, 64, 10
    loss = _linear_loss()
    params = {"w": jax.random.normal(key, (F, C)) * 0.3,
              "b": jnp.zeros((C,))}
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, F))
    y = jax.random.randint(jax.random.fold_in(key, 2), (B,), 0, C)
    nk = jax.random.fold_in(key, 3)
    got = dispatch.dp_round(loss, params, x, y, nk, clip=0.8, sigma=1.1,
                            kernels=KernelConfig(backend="auto"))
    want = dp_lib.dp_gradients(loss, params, {"x": x, "y": y}, nk,
                               clip=0.8, sigma=1.1)
    for k in want:
        assert np.array_equal(np.asarray(got[k]), np.asarray(want[k]))


def test_client_grad_routes_linear_dp_through_dp_round(key, monkeypatch):
    """The engine's per-client DP grad takes the fused entry point for the
    linear model (and only for configs the closed form covers)."""
    from repro.baselines import common
    from repro.config import DPConfig
    B, F, C = 8, 32, 4
    params = {"w": jax.random.normal(key, (F, C)), "b": jnp.zeros((C,))}
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, F))
    y = jax.random.randint(jax.random.fold_in(key, 2), (B,), 0, C)
    calls = []
    orig = dispatch.dp_round

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(dispatch, "dp_round", spy)
    dp_cfg = DPConfig(enabled=True, clip_norm=0.7)
    g = common.client_grad(common.linear_apply, params, x, y, key,
                           dp_cfg=dp_cfg, sigma=0.9)
    assert calls and np.isfinite(np.asarray(g["w"])).all()
    # microbatching is outside the closed form: composed pipeline instead
    calls.clear()
    dp_cfg = DPConfig(enabled=True, clip_norm=0.7, per_example_chunk=4)
    common.client_grad(common.linear_apply, params, x, y, key,
                       dp_cfg=dp_cfg, sigma=0.9)
    assert not calls


def test_dp_round_sigma_without_key_raises(key):
    params = {"w": jax.random.normal(key, (8, 3)), "b": jnp.zeros((3,))}
    x = jax.random.normal(key, (4, 8))
    y = jnp.zeros((4,), jnp.int32)
    with pytest.raises(ValueError, match="PRNG key"):
        dispatch.dp_round(_linear_loss(), params, x, y, clip=1.0, sigma=0.5)


# ---------------------------------------------------------------------------
# halo mix-step row-block autotuning (million-client PR: paged cohorts make
# m per shard small and variable, so the block width is tuned, not fixed)
# ---------------------------------------------------------------------------

def test_mix_halo_candidates_respect_row_count():
    # (0,) — the untiled pre-autotune lowering — is always a candidate, and
    # a block never covers the whole row range (that IS the untiled case)
    assert dispatch._mix_halo_candidates(4) == [(0,)]
    assert dispatch._mix_halo_candidates(8) == [(0,)]
    assert dispatch._mix_halo_candidates(64) == [(0,), (8,), (16,), (32,)]
    assert dispatch._mix_halo_candidates(256) == [
        (0,), (8,), (16,), (32,), (64,), (128,)]


def test_mix_halo_tiles_policy():
    shape = (64, 16, 3, 128)
    # explicit tile bypasses autotune entirely
    cfg = KernelConfig(mix_halo_tile=16)
    assert dispatch.mix_halo_tiles(shape, jnp.float32, cfg, "pallas") == (16,)
    # non-pallas backends never autotune: untiled static default
    cfg = KernelConfig()
    assert dispatch.mix_halo_tiles(shape, jnp.float32, cfg, "ref") == (0,)
    cfg = KernelConfig(autotune=False)
    assert dispatch.mix_halo_tiles(shape, jnp.float32, cfg, "pallas") == (0,)


def test_mix_halo_autotune_cached_per_shape():
    dispatch.clear_autotune_cache()
    cfg = KernelConfig(autotune=True, autotune_trials=1)
    got = dispatch.mix_halo_tiles((32, 8, 2, 16), jnp.float32, cfg, "pallas")
    assert got in dispatch._mix_halo_candidates(32)
    again = dispatch.mix_halo_tiles((32, 8, 2, 16), jnp.float32, cfg,
                                    "pallas")
    assert again == got
    assert dispatch.autotune_cache_stats()["hits"] >= 1


def test_halo_mix_probe_tiled_bit_equal_to_untiled(key):
    """Row blocking only changes the lowering — every tile width must give
    bit-identical rows (the property that lets the tuned width vary freely
    without breaking the sharded engine's bit-exactness contract)."""
    m, H, d, f = 24, 6, 3, 10
    buf = jax.random.normal(key, (m + H, f), jnp.float32)
    idx = jax.random.randint(jax.random.fold_in(key, 1), (m, d), 0, m + H)
    s = jax.random.uniform(jax.random.fold_in(key, 2), (m,))
    w = jax.random.normal(jax.random.fold_in(key, 3), (m, d)) * 0.1
    ref = dispatch._halo_mix_probe(buf, idx, s, w, 0)
    for tm in (1, 7, 8, 16, 24, 100):
        np.testing.assert_array_equal(
            np.asarray(dispatch._halo_mix_probe(buf, idx, s, w, tm)),
            np.asarray(ref))
