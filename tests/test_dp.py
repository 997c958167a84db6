"""DP machinery: Eqs. 10–12, accountant, per-example vs microbatch grads."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dp as dp_lib
from repro.utils.pytree import global_norm, tree_unflatten_concat


def test_clip_bounds_norm(key):
    tree = {"a": jax.random.normal(key, (8, 8)) * 10, "b": jnp.ones((3,)) * 5}
    clipped, norm = dp_lib.clip_by_global_norm(tree, 1.0)
    assert float(global_norm(clipped)) <= 1.0 + 1e-5
    small = jax.tree_util.tree_map(lambda t: t * 1e-3, tree)
    clipped2, _ = dp_lib.clip_by_global_norm(small, 1.0)
    # below the clip, gradients pass through unchanged
    np.testing.assert_allclose(np.asarray(clipped2["a"]), np.asarray(small["a"]),
                               rtol=1e-6)


def test_noble_sigma_eq12_formula():
    """σ_g = s·sqrt(l T K log(2Tl/δ) log(2/δ)) / (ε sqrt(M'))."""
    eps, delta, s, T, K = 15.0, 1e-3, 0.5, 100, 2
    got = dp_lib.noble_sigma(eps, delta, sample_rate=s, rounds=T, local_steps=K)
    want = s * math.sqrt(1 * T * K * math.log(2 * T / delta)
                         * math.log(2 / delta)) / eps
    assert abs(got - want) < 1e-9
    # tighter ε ⇒ more noise; more rounds ⇒ more noise
    assert dp_lib.noble_sigma(3.0, delta, rounds=T) > got
    assert dp_lib.noble_sigma(eps, delta, rounds=4 * T) > got


def test_rdp_accountant_monotone():
    e1 = dp_lib.rdp_epsilon(sigma=2.0, q=0.1, steps=100, delta=1e-5)
    e2 = dp_lib.rdp_epsilon(sigma=4.0, q=0.1, steps=100, delta=1e-5)
    e3 = dp_lib.rdp_epsilon(sigma=2.0, q=0.1, steps=400, delta=1e-5)
    assert e2 < e1 < e3


def test_calibrate_sigma_achieves_target():
    target = 8.0
    sigma = dp_lib.calibrate_sigma(target, 1e-5, q=0.2, steps=200)
    eps = dp_lib.rdp_epsilon(sigma, 0.2, 200, 1e-5)
    assert eps <= target + 1e-2
    # not absurdly conservative either
    eps_lo = dp_lib.rdp_epsilon(sigma * 0.8, 0.2, 200, 1e-5)
    assert eps_lo > target


def _quad_loss(params, batch):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2)


def test_dp_gradients_zero_noise_matches_clipped_mean(key):
    params = {"w": jax.random.normal(key, (4, 2))}
    x = jax.random.normal(jax.random.fold_in(key, 1), (8, 4)) * 3
    y = jax.random.normal(jax.random.fold_in(key, 2), (8, 2))
    g = dp_lib.dp_gradients(_quad_loss, params, {"x": x, "y": y},
                            jax.random.fold_in(key, 3), clip=0.1, sigma=0.0)
    # per-example clipped mean: norm of the mean must be <= clip
    assert float(global_norm(g)) <= 0.1 + 1e-6


def test_dp_gradients_sensitivity_bound(key):
    """Core DP invariant: swapping ONE example changes the (pre-noise)
    clipped-mean gradient by at most 2C/n in l2."""
    n, C = 16, 0.5
    params = {"w": jax.random.normal(key, (4, 2))}
    x = jax.random.normal(jax.random.fold_in(key, 1), (n, 4)) * 5
    y = jax.random.normal(jax.random.fold_in(key, 2), (n, 2))
    x2 = x.at[0].set(-x[0] * 7)
    y2 = y.at[0].set(y[0] + 11)
    g1 = dp_lib.dp_gradients(_quad_loss, params, {"x": x, "y": y},
                             key, clip=C, sigma=0.0)
    g2 = dp_lib.dp_gradients(_quad_loss, params, {"x": x2, "y": y2},
                             key, clip=C, sigma=0.0)
    diff = jax.tree_util.tree_map(lambda a, b: a - b, g1, g2)
    assert float(global_norm(diff)) <= 2 * C / n + 1e-6


def test_dp_gradients_noise_statistics(key):
    """Eq. 11 noise scale: std ≈ 2Cσ/n on each coordinate."""
    params = {"w": jnp.zeros((1, 1))}
    batch = {"x": jnp.zeros((4, 1)), "y": jnp.zeros((4, 1))}
    C, sigma, n = 1.0, 3.0, 4
    samples = []
    for i in range(300):
        g = dp_lib.dp_gradients(_quad_loss, params, batch,
                                jax.random.fold_in(key, i), clip=C, sigma=sigma)
        samples.append(float(g["w"][0, 0]))
    std = np.std(samples)
    expect = 2 * C * sigma / n
    assert 0.8 * expect < std < 1.2 * expect


def test_microbatch_matches_per_example_when_mb_is_1(key):
    """microbatches == n reduces to per-example clipping."""
    n = 8
    params = {"w": jax.random.normal(key, (3, 2))}
    x = jax.random.normal(jax.random.fold_in(key, 1), (n, 3)) * 4
    y = jax.random.normal(jax.random.fold_in(key, 2), (n, 2))
    k = jax.random.fold_in(key, 3)
    g_pe = dp_lib.dp_gradients(_quad_loss, params, {"x": x, "y": y}, k,
                               clip=0.3, sigma=0.0, microbatches=0)
    g_mb = dp_lib.dp_gradients(_quad_loss, params, {"x": x, "y": y}, k,
                               clip=0.3, sigma=0.0, microbatches=n)
    np.testing.assert_allclose(np.asarray(g_pe["w"]), np.asarray(g_mb["w"]),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("chunk", [0, 4])
@pytest.mark.parametrize("temperature", [1.0, 2.0])
@pytest.mark.parametrize("alpha", [0.0, 0.5, 1.0])
def test_affine_closed_form_matches_per_example(key, alpha, temperature,
                                                 chunk):
    """The linear model's DP gradient from per-example logit gradients
    equals the per-example route's on P4's proxy loss (Eq. 8), clipped and
    unclipped examples alike, and draws the same noise from the same key."""
    from repro.core import distill
    from repro.core.small_models import linear_apply
    n, feat, classes = 16, 12, 5
    x = (jax.random.normal(jax.random.fold_in(key, 1), (n, feat))
         * jnp.linspace(0.05, 3.0, n)[:, None])
    y = jax.random.randint(jax.random.fold_in(key, 2), (n,), 0, classes)
    proxy = {"w": 0.3 * jax.random.normal(jax.random.fold_in(key, 3),
                                          (feat, classes)),
             "b": 0.1 * jax.random.normal(jax.random.fold_in(key, 4),
                                          (classes,))}
    private = {"w": 0.3 * jax.random.normal(jax.random.fold_in(key, 5),
                                            (feat, classes)),
               "b": jnp.zeros((classes,))}

    def proxy_obj(w, batch):
        return distill.proxy_loss(linear_apply(w, batch["x"]),
                                  linear_apply(private, batch["x"]),
                                  batch["y"], alpha, temperature)

    def one_loss(z, t, label):
        return distill.proxy_loss(z[None], t[None], label[None], alpha,
                                  temperature)

    dl = jax.vmap(jax.grad(one_loss))(linear_apply(proxy, x),
                                      linear_apply(private, x), y)
    norms = np.sqrt(np.sum(np.square(dl), -1)
                    * (1 + np.sum(np.square(x), -1)))
    clip = float(np.median(norms))
    assert (norms > 1.01 * clip).any() and (norms < 0.99 * clip).any()
    k = jax.random.fold_in(key, 6)

    def both(sigma):
        pe = dp_lib.dp_gradients(proxy_obj, proxy, {"x": x, "y": y}, k,
                                 clip=clip, sigma=sigma,
                                 per_example_chunk=chunk)
        cf = tree_unflatten_concat(
            dp_lib.dp_affine_flat(x, dl, k, clip=clip, sigma=sigma), proxy)
        return pe, cf

    pe0, cf0 = both(0.0)
    pe1, cf1 = both(1.3)
    for name in ("b", "w"):
        assert cf0[name].shape == pe0[name].shape
        assert cf0[name].dtype == pe0[name].dtype
        np.testing.assert_allclose(np.asarray(cf0[name]),
                                   np.asarray(pe0[name]), rtol=1e-5,
                                   atol=1e-7)
        # the same draw: each route's noise is its noised minus its clean
        # result; they differ only by the rounding of that one add
        noise_pe = np.asarray(pe1[name]) - np.asarray(pe0[name])
        noise_cf = np.asarray(cf1[name]) - np.asarray(cf0[name])
        ulp = (np.finfo(np.float32).eps
               * np.max(np.abs(np.asarray(pe1[name]))))
        assert np.max(np.abs(noise_pe)) > 1e3 * ulp
        np.testing.assert_allclose(noise_cf, noise_pe, rtol=0, atol=4 * ulp)


def _cnn_case(key, shape, n=8, classes=4, width=4):
    """A small CNN with its layer seam, a batch scaled so that examples'
    gradient norms spread, and each example's logit gradient of P4's proxy
    loss (Eq. 8) against a second model's logits."""
    from repro.core import distill
    from repro.core.small_models import make_cnn
    from repro.models.module import init_params
    specs, apply = make_cnn(shape, classes, width=width)
    proxy = init_params(specs, jax.random.fold_in(key, 3))
    private = init_params(specs, jax.random.fold_in(key, 5))
    x = (jax.random.normal(jax.random.fold_in(key, 1), (n,) + shape)
         * jnp.linspace(0.05, 3.0, n)[:, None, None, None])
    y = jax.random.randint(jax.random.fold_in(key, 2), (n,), 0, classes)

    def proxy_obj(w, batch):
        return distill.proxy_loss(apply(w, batch["x"]),
                                  apply(private, batch["x"]),
                                  batch["y"], 0.5)

    def one_loss(z, t, label):
        return distill.proxy_loss(z[None], t[None], label[None], 0.5)

    dl = jax.vmap(jax.grad(one_loss))(apply(proxy, x), apply(private, x), y)
    return apply, proxy, proxy_obj, x, y, dl


@pytest.mark.parametrize("shape", [(3, 4, 4), (5, 8, 4)])
def test_ghost_layer_norms_match_per_example_gradients(key, shape):
    """Each layer's ghost norm, from the layer's input and output gradient,
    equals the norm of that layer's explicit per-example gradient, the
    SAME-padding edge positions included (at H = W = 4 every output
    position of the second convolution touches the padding)."""
    apply, proxy, proxy_obj, x, y, dl = _cnn_case(key, shape)
    seam = apply.layer_seam
    n = x.shape[0]
    per_ex = jax.vmap(lambda xi, yi: jax.grad(proxy_obj)(
        proxy, {"x": xi[None], "y": yi[None]}))(x, y)
    _, vjp, inputs = jax.vjp(lambda p, t: seam.forward(p, x, t), proxy,
                             seam.zero_taps(n), has_aux=True)
    _, grads = vjp(dl)
    params_of = {"c1": ("c1",), "c2": ("c2",), "head": ("w", "b")}
    assert set(seam.kinds) == set(params_of)
    for name, kind in seam.kinds.items():
        want = sum(np.sum(np.square(np.asarray(per_ex[k]).reshape(n, -1)), -1)
                   for k in params_of[name])
        got = dp_lib.ghost_sq_norms({name: kind}, inputs, grads)
        assert got.shape == (n,)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5)
    total = dp_lib.ghost_sq_norms(seam.kinds, inputs, grads)
    flat = sum(np.sum(np.square(np.asarray(v).reshape(n, -1)), -1)
               for v in per_ex.values())
    np.testing.assert_allclose(np.asarray(total), flat, rtol=2e-5)


@pytest.mark.parametrize("chunk", [0, 4])
@pytest.mark.parametrize("shape", [(3, 4, 4), (5, 8, 4)])
def test_ghost_norm_route_matches_per_example(key, shape, chunk):
    """The CNN's DP gradient from per-layer ghost norms equals the
    per-example route's on P4's proxy loss, clipped and unclipped examples
    alike, with the norms in one batch or in blocks, and draws the same
    noise from the same key."""
    apply, proxy, proxy_obj, x, y, dl = _cnn_case(key, shape)
    n = x.shape[0]
    per_ex = jax.vmap(lambda xi, yi: jax.grad(proxy_obj)(
        proxy, {"x": xi[None], "y": yi[None]}))(x, y)
    norms = np.sqrt(sum(np.sum(np.square(np.asarray(v).reshape(n, -1)), -1)
                        for v in per_ex.values()))
    clip = float(np.median(norms))
    assert (norms > 1.01 * clip).any() and (norms < 0.99 * clip).any()
    k = jax.random.fold_in(key, 6)

    def both(sigma):
        pe = dp_lib.dp_gradients(proxy_obj, proxy, {"x": x, "y": y}, k,
                                 clip=clip, sigma=sigma,
                                 per_example_chunk=chunk)
        gh, logits = dp_lib.dp_ghost_gradients(
            apply.layer_seam, proxy, x, lambda z: dl, k, clip=clip,
            sigma=sigma, block=chunk)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(apply(proxy, x)), rtol=1e-6,
                                   atol=1e-6)
        return pe, gh

    pe0, gh0 = both(0.0)
    pe1, gh1 = both(1.3)
    for name in proxy:
        assert gh0[name].shape == pe0[name].shape
        assert gh0[name].dtype == pe0[name].dtype
        # float32 sums over examples and positions in another order: an
        # error of a few ulps of the gradient's largest entry
        scale = float(np.max(np.abs(np.asarray(pe0[name]))))
        np.testing.assert_allclose(np.asarray(gh0[name]),
                                   np.asarray(pe0[name]), rtol=1e-5,
                                   atol=1e-5 * scale)
        noise_pe = np.asarray(pe1[name]) - np.asarray(pe0[name])
        noise_gh = np.asarray(gh1[name]) - np.asarray(gh0[name])
        ulp = (np.finfo(np.float32).eps
               * np.max(np.abs(np.asarray(pe1[name]))))
        assert np.max(np.abs(noise_pe)) > 1e3 * ulp
        np.testing.assert_allclose(noise_gh, noise_pe, rtol=0, atol=4 * ulp)
