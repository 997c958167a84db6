"""The P4 co-train path's language model on the CPU: the dropless expert
share against the uncut layer, its routing under forced imbalance, the
attention window, YaRN against its formula, and the per-layer norm route
against per-example gradients."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import MoEConfig, YarnConfig
from repro.core import dp
from repro.core.lm import lm_config, make_lm
from repro.models import moe
from repro.models.attention import blocked_attention
from repro.models.module import init_params
from repro.models.rope import apply_rope, yarn_freqs

# float32 sums taken in another order (grouped products and scatter-adds
# against one dense contraction): a few ulps of values of order 1
F32_REL = 1e-5

E, K, D, F = 16, 4, 12, 8


def _experts(key, E=E):
    k1, k2, k3 = jax.random.split(key, 3)
    return (jax.random.normal(k1, (E, D, F)) / math.sqrt(D),
            jax.random.normal(k2, (E, D, F)) / math.sqrt(D),
            jax.random.normal(k3, (E, F, D)) / math.sqrt(F))


def _dense_moe(x, logits, w, first, count, k=K):
    """Every expert of [first, first + count) on every token, weighted by
    its renormalized top-k gate (0 where the token did not choose it)."""
    gate, ids = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
    gate = gate / jnp.sum(gate, -1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(first, first + count):
        we = jnp.sum(jnp.where(ids == e, gate, 0.0), -1)
        y = (jax.nn.silu(x @ w[0][e]) * (x @ w[1][e])) @ w[2][e]
        out = out + we[:, None] * y
    return out


def _share(x, logits, w, first, count):
    cfg = MoEConfig(num_experts=E, experts_per_token=K, held_first=first,
                    held_count=count)
    route = moe.share_route(logits, cfg)
    part = [t[first:first + count] for t in w]
    return moe.expert_share(x, route, *part, jnp.zeros((0,))), route


def test_expert_shares_sum_to_the_uncut_layer():
    """Four chips' shares of 4 experts each add up to the whole layer of
    16: the router's softmax and top-k are every chip's alike, and each
    share adds only its own experts' part."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (40, D))
    logits = jax.random.normal(jax.random.fold_in(key, 1), (40, E))
    w = _experts(jax.random.fold_in(key, 2))
    parts = [_share(x, logits, w, first, 4)[0] for first in (0, 4, 8, 12)]
    whole = _share(x, logits, w, 0, E)[0]
    dense = _dense_moe(x, logits, w, 0, E)
    np.testing.assert_allclose(sum(parts), dense, rtol=F32_REL, atol=F32_REL)
    np.testing.assert_allclose(whole, dense, rtol=F32_REL, atol=F32_REL)
    for first, part in zip((0, 4, 8, 12), parts):
        np.testing.assert_allclose(part, _dense_moe(x, logits, w, first, 4),
                                   rtol=F32_REL, atol=F32_REL)


def test_forced_imbalance_drops_no_token(monkeypatch):
    """Every token chooses held expert 1: all T tokens take its rows (no
    capacity), in chunks of 6 tokens, and the share is the dense
    reference's, forward and backward."""
    monkeypatch.setattr(moe, "ROW_CHUNK", 24)
    key = jax.random.PRNGKey(3)
    T = 66
    x = jax.random.normal(key, (T, D))
    logits = jax.random.normal(jax.random.fold_in(key, 1), (T, E))
    logits = logits.at[:, 1].add(50.0)
    w = _experts(jax.random.fold_in(key, 2))
    out, route = _share(x, logits, w, 0, 4)
    assert int(jnp.sum(route.slot == 1)) == T
    assert moe._chunk_tokens(T, K) == 6
    np.testing.assert_allclose(out, _dense_moe(x, logits, w, 0, 4),
                               rtol=F32_REL, atol=F32_REL)

    def loss(x, logits, w, f):
        return jnp.sum(jnp.sin(f(x, logits, w)))
    got = jax.grad(loss, argnums=(0, 1, 2))(
        x, logits, w, lambda x, lg, w: _share(x, lg, w, 0, 4)[0])
    want = jax.grad(loss, argnums=(0, 1, 2))(
        x, logits, w, lambda x, lg, w: _dense_moe(x, lg, w, 0, 4))
    for g, h in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, h, rtol=1e-4, atol=1e-5)


def _attention_dense(q, k, v, window):
    n, s, hq, hd = q.shape
    g = hq // k.shape[2]
    kk, vv = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    sc = jnp.einsum("nqhd,nkhd->nhqk", q, kk) / math.sqrt(hd)
    pos = jnp.arange(s)
    ok = pos[None, :] <= pos[:, None]
    if window:
        ok &= pos[None, :] > pos[:, None] - window
    p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), -1)
    return jnp.einsum("nhqk,nkhd->nqhd", p, vv)


@pytest.mark.parametrize("window", [0, 5])
def test_attention_blocks_and_the_window(window):
    """Blocked attention equals the dense masked softmax; a key moved at
    position 3 changes the queries that may read it (all later ones on a
    full layer, the next ``window`` on a sliding one) and no other."""
    key = jax.random.PRNGKey(5)
    n, s, hq, kvh, hd = 2, 32, 4, 2, 8
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (n, s, h, hd))
               for i, h in enumerate((hq, kvh, kvh)))
    out = blocked_attention(q, k, v, window, 8)
    np.testing.assert_allclose(out, _attention_dense(q, k, v, window),
                               rtol=F32_REL, atol=F32_REL)
    moved = blocked_attention(q, k.at[:, 3].add(1.0), v.at[:, 3].add(1.0),
                              window, 8)
    changed = np.asarray(jnp.max(jnp.abs(moved - out), axis=(0, 2, 3)) > 0)
    reach = np.arange(s) >= 3
    if window:
        reach &= np.arange(s) < 3 + window
    np.testing.assert_array_equal(changed, reach)


def test_yarn_matches_its_formula():
    """YaRN as arXiv:2309.00071 and HF's ``rope_parameters`` define it,
    transcribed in float64 at Mellum2's full-layer parameters: the ramp
    over the channels between the correction dimensions of β_fast and
    β_slow rotations at the original context blends θ's frequencies (kept
    where the ramp is 0) with those divided by the factor."""
    hd, theta = 128, 500000.0
    y = YarnConfig(factor=16.0, original_max_position=8192, beta_fast=32.0,
                   beta_slow=1.0, attention_factor=1.2772588722239782)
    inv, scale = yarn_freqs(hd, theta, y)

    def dim_of(rotations):
        return hd * math.log(8192 / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))
    low, high = math.floor(dim_of(32.0)), math.ceil(dim_of(1.0))
    want = []
    for i in range(hd // 2):
        freq = theta ** (-2 * i / hd)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(freq / 16.0 * ramp + freq * (1.0 - ramp))
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert (low, high) == (max(low, 0), min(high, hd - 1))
    assert scale == pytest.approx(0.1 * math.log(16.0) + 1.0, rel=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, hd))
    pos = jnp.arange(5)[None]
    np.testing.assert_allclose(
        apply_rope(x, pos, theta, freqs=jnp.asarray(inv), scale=scale),
        scale * apply_rope(x, pos, theta, freqs=jnp.asarray(inv)),
        rtol=F32_REL, atol=F32_REL)


def test_dense_norm_is_each_sequence_gradient_norm():
    """A dense layer's per-sequence squared norm against the squared norm
    of that sequence's own weight gradient, from ``jax.grad``."""
    key = jax.random.PRNGKey(7)
    a = jax.random.normal(key, (3, 10, 6))
    g = jax.random.normal(jax.random.fold_in(key, 1), (3, 10, 5))
    w = jnp.zeros((6, 5))
    grads = jax.vmap(lambda ai, gi: jax.grad(
        lambda v: jnp.sum((ai @ v) * gi))(w))(a, g)
    np.testing.assert_allclose(dp.seq_dense_sq_norms(a, g),
                               jnp.sum(jnp.square(grads), (1, 2)),
                               rtol=F32_REL)


LM = dict(num_layers=4, d_model=32, num_heads=4, num_kv_heads=2, head_dim=8,
          d_ff=16, vocab_size=300, window=4, rope_theta=500000.0,
          layer_pattern=["sliding", "sliding", "sliding", "full"],
          norm_eps=1e-6,
          yarn=dict(factor=16.0, original_max_position=8192, beta_fast=32.0,
                    beta_slow=1.0, attention_factor=1.2772588722239782),
          moe=dict(num_experts=16, experts_per_token=4, held_first=4,
                   held_count=4))


def _loss(apply, p, t):
    z = apply(p, t[None, :-1])
    return -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(z),
                                         t[None, 1:, None], -1))


@pytest.mark.parametrize("chunk", [1024, 24])
def test_layer_norm_route_matches_per_example_gradients(monkeypatch, chunk):
    """Each sequence's squared gradient norm left on the probe, and the
    clipped sum, against each sequence's own gradient; with the expert
    buffer in one chunk and in several."""
    monkeypatch.setattr(moe, "ROW_CHUNK", chunk)
    specs, apply = make_lm(lm_config(LM))
    key = jax.random.PRNGKey(11)
    p = init_params(specs, key)
    t = jax.random.randint(jax.random.fold_in(key, 1), (3, 17), 0, 300)
    per_ex = jax.lax.map(lambda r: jax.grad(
        lambda q: _loss(apply, q, r))(p), t)
    want = sum(jnp.sum(jnp.square(v).reshape(3, -1), -1)
               for v in per_ex.values())

    def logit_grads(z):
        return jax.vmap(jax.grad(lambda zz, y: -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(zz), y[:, None], -1))))(z, t[:, 1:])
    logits, vjp = jax.vjp(lambda q, z: apply.layer_forward(
        q, t[:, :-1], z), p, jnp.zeros((3,)))
    _, sq = vjp(logit_grads(logits))
    np.testing.assert_allclose(sq, want, rtol=1e-4)
    clip = float(jnp.sqrt(jnp.median(want)))
    total, _ = dp.dp_layer_clipped_sum(apply.layer_forward, p, t[:, :-1],
                                       logit_grads, clip=clip, denom=3.0)
    s = jnp.minimum(1.0, clip / jnp.sqrt(want)) / 3.0
    for k in p:
        want_k = jnp.tensordot(s, per_ex[k], 1)
        # float32 sums in another order: a few ulps of the leaf's largest
        np.testing.assert_allclose(total[k], want_k, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(want_k))))


def test_dense_stack_takes_the_period_of_layer_types():
    """The dry-run decoder scans one period of layer types: a full layer
    in the period reads keys a sliding one cannot."""
    from repro.config import ModelConfig
    from repro.models import transformer
    base = ModelConfig(num_layers=4, d_model=32, num_heads=4, num_kv_heads=2,
                       d_ff=64, vocab_size=50, window=2, dtype="float32",
                       logits_dtype="float32", remat="none")
    mixed = ModelConfig(**dict(base.__dict__, layer_pattern=(
        "sliding", "full")))
    p = init_params(transformer.model_specs(base), jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, 50)
    a = transformer.forward(p, base, {"tokens": tokens})[0]
    b = transformer.forward(p, mixed, {"tokens": tokens})[0]
    moved = tokens.at[0, 0].set((tokens[0, 0] + 1) % 50)
    a2 = transformer.forward(p, base, {"tokens": moved})[0]
    b2 = transformer.forward(p, mixed, {"tokens": moved})[0]
    # four sliding layers of window 2 reach 4 positions ahead; a full layer
    # carries position 0 to the last one
    assert float(jnp.max(jnp.abs(a2 - a)[0, -1])) == 0.0
    assert float(jnp.max(jnp.abs(b2 - b)[0, -1])) > 0.0


def test_a_language_model_round_names_its_layers():
    """A co-train chunk of a two-client federation of the language model:
    the op -> layer table holds every layer, the three of the model among
    them, and the ``dp.path`` probe counts the per-layer norm route."""
    from repro.config import DPConfig, P4Config, RunConfig, TrainConfig
    from repro.core.p4 import P4Strategy, P4Trainer
    from repro.engine import Engine, FederatedData
    from repro.obs import LAYERS, probe_deltas
    from repro.obs.layers import MODEL_LAYERS, op_layers
    cfg = RunConfig(dp=DPConfig(epsilon=8.0, delta=1e-5, per_example_chunk=2),
                    p4=P4Config(group_size=2), train=TrainConfig(
                        learning_rate=1e-3))
    trainer = P4Trainer(feat_dim=16, num_classes=300, cfg=cfg, model="lm",
                        lm=LM)
    strategy = P4Strategy(trainer=trainer)
    strategy.set_groups([[0, 1]], 2)
    rows = jax.random.randint(jax.random.PRNGKey(2), (2, 6, 17), 0, 300)
    y = jnp.zeros((2, 6), jnp.int32)
    data = FederatedData(rows[:, :4], y[:, :4], rows[:, 4:], y[:, 4:])
    state = trainer.init_clients(jax.random.PRNGKey(0), 2)
    with probe_deltas("dp.path") as d:
        state, metrics, _ = Engine(strategy, eval_every=2).run_rounds(
            state, data, jax.random.PRNGKey(1), 0, 2, 4)
    assert d["dp.path"]["layer_norms"] >= 1
    assert np.all(np.isfinite(np.asarray(metrics["proxy_loss"])))
    table = op_layers()
    seen = {name for layers in table.values() for name in layers}
    assert seen == set(LAYERS) and set(MODEL_LAYERS) <= seen
    acc = trainer.evaluate(state, data.test_x, data.test_y)
    assert acc.shape == (2,) and bool(jnp.all((acc >= 0) & (acc <= 1)))


def test_flash_kernels_match_the_ref_path_in_the_dp_step(monkeypatch):
    """The language model with its attention on the Pallas kernels
    (interpret) against the ref backend (``blocked_attention``), at this
    file's CPU size with heads of 128 (the kernels' lanes) and blocks of 8
    of the 16 tokens: the logits, each sequence's squared norm the
    per-layer route leaves on its probe, and the clipped sum of
    ``dp.dp_layer_clipped_sum`` agree to float32 sums in another order.
    So the kernels' custom VJP composes with the layer checkpoint, the
    probes and both backwards of one forward."""
    from repro.config import KernelConfig
    from repro.kernels.flash_attention import kernel as fl_kernel
    from repro.obs import probe_deltas
    monkeypatch.setattr(fl_kernel, "BLOCK_Q", 8)
    monkeypatch.setattr(fl_kernel, "BLOCK_K", 8)
    cfg = lm_config(dict(LM, head_dim=128))
    key = jax.random.PRNGKey(13)
    t = jax.random.randint(jax.random.fold_in(key, 1), (3, 17), 0, 300)

    def logit_grads(z):
        return jax.vmap(jax.grad(lambda zz, y: -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(zz), y[:, None], -1))))(z, t[:, 1:])

    got = {}
    for backend in ("interpret", "ref"):
        specs, apply = make_lm(cfg, KernelConfig(backend=backend))
        p = init_params(specs, key)
        with probe_deltas("kernels.backend") as d:
            logits, vjp = jax.vjp(lambda q, z: apply.layer_forward(
                q, t[:, :-1], z), p, jnp.zeros((3,)))
            _, sq = vjp(logit_grads(logits))
            clip = float(jnp.sqrt(jnp.median(sq)))
            total, _ = dp.dp_layer_clipped_sum(
                apply.layer_forward, p, t[:, :-1], logit_grads, clip=clip,
                denom=3.0)
        assert d["kernels.backend"][f"flash_attention:{backend}"] > 0
        got[backend] = (apply(p, t[:, :-1]), logits, sq, clip, total)
    flash, ref = got["interpret"], got["ref"]
    for a, b in zip(flash[:2], ref[:2]):
        np.testing.assert_allclose(a, b, rtol=F32_REL,
                                   atol=F32_REL * float(jnp.max(jnp.abs(b))))
    np.testing.assert_allclose(flash[2], ref[2], rtol=1e-4)
    assert flash[3] == pytest.approx(ref[3], rel=1e-4)
    assert float(jnp.min(ref[2])) < ref[3] ** 2 < float(jnp.max(ref[2]))
    for k in ref[4]:
        np.testing.assert_allclose(
            flash[4][k], ref[4][k], rtol=1e-4,
            atol=1e-5 * float(jnp.max(jnp.abs(ref[4][k]))))
