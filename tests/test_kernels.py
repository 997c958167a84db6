"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode
(brief §c: per kernel, sweep shapes/dtypes, assert_allclose vs ref)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.dp_clip import ops as dp_ops, ref as dp_ref
from repro.kernels.dp_round import ops as dpr_ops, ref as dpr_ref
from repro.kernels.flash_attention import ops as fl_ops, ref as fl_ref
from repro.kernels.l1_distance import ops as l1_ops, ref as l1_ref


# ---------------------------------------------------------------------------
# dp_clip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,D", [(4, 64), (8, 1000), (16, 4096), (5, 333)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dp_clip_flat_sweep(key, B, D, dtype):
    x = (jax.random.normal(key, (B, D)) * 3).astype(dtype)
    got = dp_ops.clip_accumulate_flat(x, 0.9, tb=4, td=256)
    want = dp_ref.clip_accumulate(x, 0.9)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_dp_clip_tree(key):
    tree = {"w": jax.random.normal(key, (6, 10, 3)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (6, 7))}
    got = dp_ops.clip_accumulate_tree(tree, 0.5)
    # oracle via flat path
    from repro.utils.pytree import tree_flatten_concat
    flat = jax.vmap(tree_flatten_concat)(tree)
    want_flat = dp_ref.clip_accumulate(flat, 0.5)
    got_flat = jnp.concatenate([got["b"].ravel(), got["w"].ravel()])
    # tree order: dict sorted keys -> b then w
    np.testing.assert_allclose(np.asarray(got_flat), np.asarray(want_flat),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# l1_distance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,D", [(4, 128), (10, 500), (16, 2048), (7, 129)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_l1_sweep(key, M, D, dtype):
    w = (jax.random.normal(key, (M, D)) * 2).astype(dtype)
    got = l1_ops.pairwise_l1(w, tm=4, td=128)
    want = l1_ref.pairwise_l1(w)
    tol = 1e-4 if dtype == jnp.float32 else 0.5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,d,blocks", [(128, 32, (64, 64)), (256, 64, (128, 64)),
                                        (256, 32, (64, 128))])
@pytest.mark.parametrize("window", [0, 100])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_sweep(key, S, d, blocks, window, dtype):
    BH = 4
    q = jax.random.normal(key, (BH, S, 1, d)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (BH, S, 1, d)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (BH, S, 1, d)).astype(dtype)
    got = fl_ops.flash_attention(q, k, v, window=window, block_q=blocks[0],
                                 block_k=blocks[1])[:, :, 0]
    want = fl_ref.attention(q[:, :, 0], k[:, :, 0], v[:, :, 0], causal=True,
                            window=window)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_flash_gqa_wrapper(key):
    b, s, hq, hkv, d = 2, 128, 4, 2, 32
    q = jax.random.normal(key, (b, s, hq, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d))
    got = fl_ops.flash_attention(q, k, v, block_q=64, block_k=64)
    kx, vx = jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2)
    def bh(t):
        return t.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    want = fl_ref.attention(bh(q), bh(kx), bh(vx)).reshape(b, hq, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_matches_model_chunked_path(key):
    """The Pallas kernel and the pure-JAX chunked path agree (same algorithm,
    two realizations — kernel is the TPU target, chunked is the dry-run path)."""
    from repro.config import ModelConfig
    from repro.models.attention import _chunked_attention
    cfg = ModelConfig(d_model=64, num_heads=4, num_kv_heads=4, vocab_size=64,
                      dtype="float32")
    b, s, h, d = 1, 256, 4, 16
    q = jax.random.normal(key, (b, s, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, d))
    chunked = _chunked_attention(q, k, v, cfg, window=0, q_chunk=64, kv_chunk=64)
    flash = fl_ops.flash_attention(q, k, v, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(flash),
                               rtol=2e-5, atol=2e-5)


def _gqa(key, n, s, hq, kvh, d):
    return (jax.random.normal(jax.random.fold_in(key, i), (n, s, h, d))
            for i, h in enumerate((hq, kvh, kvh)))


def _dense_attention(q, k, v, window):
    """The masked softmax over every key, at HIGHEST precision."""
    n, s, hq, d = q.shape
    g = hq // k.shape[2]
    kk, vv = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    sc = jnp.einsum("nqhd,nkhd->nhqk", q, kk,
                    precision=jax.lax.Precision.HIGHEST) / np.sqrt(d)
    pos = jnp.arange(s)
    ok = pos[None, :] <= pos[:, None]
    if window:
        ok &= pos[None, :] > pos[:, None] - window
    p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), -1)
    return jnp.einsum("nhqk,nkhd->nqhd", p, vv,
                      precision=jax.lax.Precision.HIGHEST)


# (bq, bk) and a window: 21 is no multiple of 16, so the last query block's
# reach (keys 28 on) starts inside key block 1; 40 against blocks of 32 and 16
@pytest.mark.parametrize("blocks,window", [((16, 16), 0), ((16, 32), 0),
                                           ((32, 16), 0), ((16, 16), 21),
                                           ((32, 16), 40), ((16, 32), 21)],
                         ids=["full-16x16", "full-16x32", "full-32x16",
                              "sliding21-16x16", "sliding40-32x16",
                              "sliding21-16x32"])
@pytest.mark.parametrize("g", [1, 4])
def test_flash_gradients_match_blocked_and_dense(key, blocks, window, g):
    """Forward and ``jax.grad`` with respect to q, k and v of the kernels'
    custom VJP against ``blocked_attention`` (the ref backend) and the
    dense masked softmax, on full and sliding layers, with g query heads a
    KV head."""
    from repro.models.attention import blocked_attention
    n, s, kvh, d = 2, 64, 2, 32
    q, k, v = _gqa(key, n, s, kvh * g, kvh, d)
    ct = jax.random.normal(jax.random.fold_in(key, 9), q.shape)

    def flash(q, k, v):
        return fl_ops.flash_attention(q, k, v, window=window,
                                      block_q=blocks[0], block_k=blocks[1])

    impls = {"flash": flash,
             "blocked": lambda q, k, v: blocked_attention(q, k, v, window, 8),
             "dense": lambda q, k, v: _dense_attention(q, k, v, window)}
    outs = {name: f(q, k, v) for name, f in impls.items()}
    grads = {name: jax.grad(lambda *a: jnp.sum(f(*a) * ct), (0, 1, 2))(q, k, v)
             for name, f in impls.items()}
    for other in ("blocked", "dense"):
        np.testing.assert_allclose(outs["flash"], outs[other],
                                   rtol=2e-5, atol=2e-5)
        for a, b in zip(grads["flash"], grads[other]):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("window", [0, 21])
def test_flash_keys_outside_the_window_get_no_gradient(key, window):
    """A loss on one query's output: the keys it cannot read (after it, or
    before its window) get exactly zero dk and dv, and the keys it reads
    do not; dq is zero at every other query."""
    n, s, kvh, g, d, at = 1, 64, 2, 4, 32, 50
    q, k, v = _gqa(key, n, s, kvh * g, kvh, d)

    def loss(q, k, v):
        o = fl_ops.flash_attention(q, k, v, window=window, block_q=16,
                                   block_k=16)
        return jnp.sum(o[:, at] ** 2)

    dq, dk, dv = jax.grad(loss, (0, 1, 2))(q, k, v)
    pos = np.arange(s)
    reach = pos <= at
    if window:
        reach &= pos > at - window
    for grad in (dk, dv):
        moved = np.asarray(jnp.max(jnp.abs(grad), axis=(0, 2, 3)) > 0)
        np.testing.assert_array_equal(moved, reach)
    moved_q = np.asarray(jnp.max(jnp.abs(dq), axis=(0, 2, 3)) > 0)
    np.testing.assert_array_equal(moved_q, pos == at)


# ---------------------------------------------------------------------------
# dp_round (fused local_update -> clip -> noise megakernel, linear model)
# ---------------------------------------------------------------------------

def _linear_problem(key, B, F, C):
    from repro.baselines.common import ce_loss, linear_apply
    kp, kx, ky = jax.random.split(key, 3)
    params = {"w": jax.random.normal(kp, (F, C)) * 0.3,
              "b": jax.random.normal(jax.random.fold_in(kp, 1), (C,)) * 0.1}
    x = jax.random.normal(kx, (B, F)) * 2
    y = jax.random.randint(ky, (B,), 0, C)
    return ce_loss(linear_apply), params, x, y


@pytest.mark.parametrize("B,F,C", [(3, 32, 3), (8, 130, 10), (17, 64, 10)])
@pytest.mark.parametrize("sigma", [0.0, 1.3])
def test_dp_round_closed_matches_reference(key, B, F, C, sigma):
    """The closed-form oracle reorders the autodiff sums but computes the
    same clipped-mean DP gradient — and the SAME noise bits (one canonical
    flat-noise helper, identical [b, w.ravel()] layout)."""
    loss, params, x, y = _linear_problem(key, B, F, C)
    nk = jax.random.fold_in(key, 7)
    want = dpr_ref.dp_round_reference(loss, params, x, y, nk,
                                      clip=0.8, sigma=sigma)
    got = dpr_ref.dp_round_closed(params, x, y, nk, clip=0.8, sigma=sigma)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B", [3, 8, 17])
@pytest.mark.parametrize("F", [32, 130])
@pytest.mark.parametrize("C", [3, 10])
def test_dp_round_kernel_padding_sweep(key, B, F, C):
    """Interpret-mode Pallas passes vs the closed-form oracle across batch /
    feature / class paddings (B to sublane, F to tile, C to lane)."""
    _, params, x, y = _linear_problem(key, B, F, C)
    nk = jax.random.fold_in(key, 3)
    want = dpr_ref.dp_round_closed(params, x, y, nk, clip=1.1, sigma=0.7)
    got = dpr_ops.dp_round_linear(params, x, y, nk, clip=1.1, sigma=0.7,
                                  tf=128, interpret=True)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=2e-5, atol=2e-5)


def test_dp_round_noise_bit_identical_to_canonical_helper(key):
    """With the same key the fused kernel's noise is exactly the canonical
    Eq. 11 draw added onto its own noiseless output."""
    _, params, x, y = _linear_problem(key, 8, 64, 10)
    nk = jax.random.fold_in(key, 5)
    B = x.shape[0]
    noiseless = dpr_ops.dp_round_linear(params, x, y, clip=0.9)
    noised = dpr_ops.dp_round_linear(params, x, y, nk, clip=0.9, sigma=1.3)
    flat = jnp.concatenate([noiseless["b"], noiseless["w"].ravel()])
    want = dp_ref.add_flat_noise(flat, nk, 1.3, 0.9, float(B))
    C = params["b"].shape[0]
    assert np.array_equal(np.asarray(noised["b"]), np.asarray(want[:C]))
    assert np.array_equal(np.asarray(noised["w"]),
                          np.asarray(want[C:].reshape(params["w"].shape)))
