"""End-to-end P4 behaviour (paper claims, miniature scale):
  - co-training + grouping trains to high per-client accuracy under DP;
  - similarity grouping matches clients with the same task;
  - group aggregation mixes proxies within (and only within) groups;
  - the LM-scale P4 step runs and decreases loss.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import DPConfig, P4Config, RunConfig, TrainConfig, replace
from repro.core.grouping import group_ids
from repro.core.p4 import P4Trainer, group_mean, make_p4_lm_step


def _toy_tasks(M=8, feat=20, classes=4, n=64, seed=0):
    """M clients, 2 task types: task A uses dims [0:10], task B dims [10:20]."""
    rng = np.random.default_rng(seed)
    protos = rng.normal(size=(2, classes, feat)).astype(np.float32) * 2
    protos[0, :, feat // 2:] = 0
    protos[1, :, : feat // 2] = 0
    xs, ys = [], []
    for c in range(M):
        task = c % 2
        y = rng.integers(0, classes, n)
        x = protos[task, y] + rng.normal(size=(n, feat)).astype(np.float32) * 0.5
        xs.append(x)
        ys.append(y)
    return np.stack(xs), np.stack(ys).astype(np.int32)


def _run_cfg(**kw):
    dp = kw.pop("dp", DPConfig(epsilon=15.0, rounds=40, sample_rate=0.5,
                               clip_norm=1.0))
    p4 = kw.pop("p4", P4Config(group_size=4, sample_peers=7))
    return RunConfig(dp=dp, p4=p4, train=TrainConfig(learning_rate=0.5), **kw)


def test_p4_trains_under_dp(key):
    xs, ys = _toy_tasks()
    trainer = P4Trainer(feat_dim=20, num_classes=4, cfg=_run_cfg())
    states, groups, hist = trainer.fit(xs, ys, jnp.asarray(xs), jnp.asarray(ys),
                                       rounds=40, eval_every=39)
    assert hist[-1][1] > 0.8, hist


def test_grouping_matches_tasks(key):
    """Clients with the same task type should end up grouped together."""
    xs, ys = _toy_tasks(M=8)
    trainer = P4Trainer(feat_dim=20, num_classes=4, cfg=_run_cfg())
    states = trainer.init_clients(key, 8)
    xb, yb = jnp.asarray(xs[:, :32]), jnp.asarray(ys[:, :32])
    for r in range(5):   # a few rounds so weights reflect the tasks
        states, _ = trainer.local_round(states, xb, yb, jax.random.fold_in(key, r))
    groups = trainer.form_groups(states, seed=0)
    for g in groups:
        tasks = {i % 2 for i in g}
        assert len(tasks) == 1, f"mixed group {g} (groups={groups})"


def test_aggregation_group_internal(key):
    M = 6
    tree = {"w": jax.random.normal(key, (M, 4))}
    ids = jnp.asarray([0, 0, 0, 1, 1, 1])
    out = group_mean(tree, ids, 2)
    # within-group equality
    np.testing.assert_allclose(np.asarray(out["w"][0]), np.asarray(out["w"][2]),
                               rtol=1e-6)
    # across groups different
    assert float(jnp.max(jnp.abs(out["w"][0] - out["w"][3]))) > 1e-3


def test_private_model_never_noised(key):
    """With lr applied only via DP path on the proxy, the private model of a
    zero-beta client trained on zero gradients must stay put."""
    xs, ys = _toy_tasks(M=4)
    cfg = _run_cfg(dp=DPConfig(epsilon=3.0, rounds=5, sample_rate=0.5,
                               clip_norm=1.0))
    trainer = P4Trainer(feat_dim=20, num_classes=4, cfg=cfg)
    states = trainer.init_clients(key, 4)
    # proxy params change under DP noise even with zero-information batches;
    # private params move only via clean gradients
    xb = jnp.zeros((4, 16, 20))
    yb = jnp.zeros((4, 16), jnp.int32)
    new_states, _ = trainer.local_round(states, xb, yb, key)
    dp_moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        states["proxy"], new_states["proxy"])
    assert max(jax.tree_util.tree_leaves(dp_moved)) > 0  # noise moved proxy


def test_p4_lm_step_runs_and_loss_finite(key):
    from repro.configs import get_reduced_config
    from repro.models.api import build_model
    from repro.optim import make_optimizer
    cfg = get_reduced_config("llama3.2-1b")
    api = build_model(cfg)
    G, b, s = 2, 2, 32
    train_cfg = TrainConfig(learning_rate=1e-3, warmup_steps=1, total_steps=10)
    step = make_p4_lm_step(api, api, train_cfg,
                           DPConfig(epsilon=15.0, microbatches=2, rounds=10),
                           P4Config())
    opt = make_optimizer(train_cfg)
    params = {"private": jax.vmap(api.init)(jax.random.split(key, G)),
              "proxy": jax.vmap(api.init)(jax.random.split(jax.random.fold_in(key, 1), G))}
    opt_states = {"private": jax.vmap(opt.init)(params["private"]),
                  "proxy": jax.vmap(opt.init)(params["proxy"])}
    tokens = jax.random.randint(key, (G, b, s), 0, cfg.vocab_size)
    params, opt_states, metrics = jax.jit(step)(params, opt_states,
                                                {"tokens": tokens}, key)
    assert bool(jnp.isfinite(metrics["loss"]))
    for leaf in jax.tree_util.tree_leaves(params):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))


@pytest.mark.parametrize("model", ["linear", "cnn"])
def test_dp_route_follows_model_structure(key, model):
    """The affine linear model's proxy takes the closed-form DP route; the
    CNN, whose apply carries a layer seam, takes the ghost-norm route (read
    from the ``dp.path`` probe)."""
    from repro.obs import probe_deltas
    xs, ys = _toy_tasks(M=4, feat=16)
    kw = {"cnn_shape": (1, 4, 4)} if model == "cnn" else {}
    trainer = P4Trainer(feat_dim=16, num_classes=4, cfg=_run_cfg(),
                        model=model, **kw)
    states = trainer.init_clients(key, 4)
    with probe_deltas("dp.path") as d:
        states, _ = trainer.local_round(states, jnp.asarray(xs[:, :8]),
                                        jnp.asarray(ys[:, :8]), key)
        jax.block_until_ready(states)
    routes = d["dp.path"]
    assert routes["microbatch"] == 0
    if model == "linear":
        assert routes["affine_closed_form"] > 0 and routes["per_example"] == 0
    else:
        assert routes["ghost_norms"] > 0 and routes["per_example"] == 0
        assert routes["affine_closed_form"] == 0


@pytest.mark.parametrize("local_steps", [1, 2])
@pytest.mark.parametrize("chunk", [0, 8])
def test_linear_closed_form_step_matches_per_example(key, chunk, local_steps):
    """Two DP co-training rounds of the linear trainer on the stacked affine
    step agree with the same rounds on the per-example route (the same model
    behind a wrapper the route test does not recognise as affine): both
    models' states, and the round's losses, which on both routes are Eqs. 9
    and 8 at the updated models."""
    from repro.core import distill
    from repro.core.small_models import linear_apply
    from repro.obs import probe_deltas
    xs, ys = _toy_tasks(M=4)
    cfg = _run_cfg(dp=DPConfig(epsilon=15.0, rounds=40, sample_rate=0.5,
                               clip_norm=1.0, per_example_chunk=chunk,
                               local_steps=local_steps))
    closed = P4Trainer(feat_dim=20, num_classes=4, cfg=cfg)
    per_ex = P4Trainer(feat_dim=20, num_classes=4, cfg=cfg)
    per_ex.apply_fn = lambda p, x: linear_apply(p, x)
    xb, yb = jnp.asarray(xs[:, :32]), jnp.asarray(ys[:, :32])
    s_cf = s_pe = closed.init_clients(key, 4)
    with probe_deltas("dp.path") as d_cf:
        for r in range(2):
            s_cf, m_cf = closed.local_round(s_cf, xb, yb,
                                            jax.random.fold_in(key, r))
    with probe_deltas("dp.path") as d_pe:
        for r in range(2):
            s_pe, m_pe = per_ex.local_round(s_pe, xb, yb,
                                            jax.random.fold_in(key, r))
    assert d_cf["dp.path"]["affine_stacked"] > 0
    assert d_cf["dp.path"]["affine_closed_form"] > 0
    assert d_cf["dp.path"]["per_example"] == 0
    assert d_pe["dp.path"]["affine_stacked"] == 0
    assert d_pe["dp.path"]["affine_closed_form"] == 0
    assert d_pe["dp.path"]["per_example"] > 0
    for a, b in zip(jax.tree_util.tree_leaves((s_cf, m_cf)),
                    jax.tree_util.tree_leaves((s_pe, m_pe))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
    # the round's losses are those of the models it returns
    p4c = cfg.p4
    for c in range(4):
        priv = jax.tree_util.tree_map(lambda t: t[c], s_cf["private"])
        prox = jax.tree_util.tree_map(lambda t: t[c], s_cf["proxy"])
        zp, zw = linear_apply(priv, xb[c]), linear_apply(prox, xb[c])
        np.testing.assert_allclose(
            float(m_cf["private_loss"][c]),
            float(distill.private_loss(zp, zw, yb[c], p4c.beta)),
            rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            float(m_cf["proxy_loss"][c]),
            float(distill.proxy_loss(zw, zp, yb[c], p4c.alpha)),
            rtol=1e-5, atol=1e-6)
    # both models moved: the private gradient and the proxy's DP gradient
    # took effect
    start = closed.init_clients(key, 4)
    for name in ("private", "proxy"):
        moved = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), s_cf[name],
            start[name])
        assert min(jax.tree_util.tree_leaves(moved)) > 1e-3


@pytest.mark.parametrize("local_steps", [1, 2])
@pytest.mark.parametrize("chunk", [0, 8])
def test_cnn_ghost_step_matches_per_example(key, chunk, local_steps):
    """Two DP co-training rounds of the CNN trainer on the ghost-norm route
    agree with the same rounds on the per-example route (the same CNN
    behind a wrapper that carries no layer seam): both models' states and
    the round's losses."""
    from repro.obs import probe_deltas
    xs, ys = _toy_tasks(M=4, feat=16)
    cfg = _run_cfg(dp=DPConfig(epsilon=15.0, rounds=40, sample_rate=0.5,
                               clip_norm=1.0, per_example_chunk=chunk,
                               local_steps=local_steps))
    ghost = P4Trainer(feat_dim=16, num_classes=4, cfg=cfg, model="cnn",
                      cnn_shape=(1, 4, 4))
    per_ex = P4Trainer(feat_dim=16, num_classes=4, cfg=cfg, model="cnn",
                       cnn_shape=(1, 4, 4))
    seamed = per_ex.apply_fn
    per_ex.apply_fn = lambda p, x: seamed(p, x)
    xb, yb = jnp.asarray(xs[:, :16]), jnp.asarray(ys[:, :16])
    s_gh = s_pe = ghost.init_clients(key, 4)
    with probe_deltas("dp.path") as d_gh:
        for r in range(2):
            s_gh, m_gh = ghost.local_round(s_gh, xb, yb,
                                           jax.random.fold_in(key, r))
    with probe_deltas("dp.path") as d_pe:
        for r in range(2):
            s_pe, m_pe = per_ex.local_round(s_pe, xb, yb,
                                            jax.random.fold_in(key, r))
    assert d_gh["dp.path"]["ghost_norms"] > 0
    assert d_gh["dp.path"]["per_example"] == 0
    assert d_pe["dp.path"]["ghost_norms"] == 0
    assert d_pe["dp.path"]["per_example"] > 0
    for a, b in zip(jax.tree_util.tree_leaves((s_gh, m_gh)),
                    jax.tree_util.tree_leaves((s_pe, m_pe))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
    # both models moved: the private gradient and the proxy's DP gradient
    # took effect
    start = ghost.init_clients(key, 4)
    for name in ("private", "proxy"):
        moved = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), s_gh[name],
            start[name])
        assert min(jax.tree_util.tree_leaves(moved)) > 1e-3
