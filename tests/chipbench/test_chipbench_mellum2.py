"""Mellum2's share on the P4 co-train path (``repro.core.lm`` through
``P4Trainer``) against its plain reference (``chipbench/models/mellum2.py``
and ``chipbench/reference.py``) on seeded random weights, at the kind's CPU
size. On the CPU both sides compute in float32 at full precision, so they
differ by the order of float32 sums alone (blocked against whole
contractions, grouped expert products against the dense weighted sum)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, program as prog_lib
from chipbench import reference as ref
from chipbench_util import shrink

SEED = 2 ** 33 + 77
# float32 sums in another order, through a few layers and a softmax
REL = 2e-5


@pytest.fixture(scope="module")
def cell():
    return shrink(harness.load_cell("mellum2-ep8.m1-s8k-b4"))


def _program(cell, cfg):
    return prog_lib.build(cfg, cell["mix"], cell["kind"].trainer_kwargs(cfg),
                          {"backend": "ref"})


def _data(cell):
    keys = harness.run_keys(SEED)
    return cell["kind"].make_data(cell["mix"], cell["cfg"], keys["data"], SEED)


def _perturbed(kind, cfg, key):
    """The kind's initialization with its norm scales moved off 1, so that
    every leaf's gradient is exercised."""
    p = kind.init_model(cfg, key)
    return {k: v * (1 + 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                                v.shape))
            if k.endswith("norm") else v for i, (k, v) in enumerate(
                sorted(p.items()))}


@pytest.mark.parametrize("types", [
    ["sliding_attention", "sliding_attention"],
    ["full_attention", "full_attention"],
    ["sliding_attention", "full_attention"]],
    ids=["sliding", "full", "period"])
@pytest.mark.parametrize("q_block", [256, 4], ids=["one-block", "blocks"])
def test_logits_match_the_reference(cell, types, q_block, monkeypatch):
    """At 16 tokens, attention in one block of queries, and in four (a
    sliding layer's blocks reading only the keys of their window)."""
    import repro.core.lm as lm
    monkeypatch.setattr(lm, "Q_BLOCK", q_block)
    kind = cell["kind"]
    cfg = dict(cell["cfg"], layer_types=types)
    p = _program(cell, cfg)
    params = _perturbed(kind, cfg, jax.random.PRNGKey(1))
    rows = _data(cell)["train_x"][0]
    want = kind.apply(cfg, params, rows, ref.HIGHEST)["logits"]
    got = p.trainer.apply_fn(params, kind.token_ids(rows)[:, :-1])
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=REL, atol=REL * scale)


def _client(cell, state):
    return {m: jax.tree_util.tree_map(lambda t: t[0], state[m])
            for m in ("private", "proxy")}


@pytest.mark.parametrize("noise", [False, True], ids=["clipped", "noised"])
def test_one_round_matches_the_reference(cell, noise):
    """One client's local step and its end-of-step losses: the private
    model's gradient, the proxy's clipped mean (alone, with σ = 0, where
    the noise would hide it) and with the same noise draw."""
    kind, cfg, mix = cell["kind"], cell["cfg"], cell["mix"]
    p = _program(cell, cfg)
    if not noise:
        p.trainer.sigma = 0.0
    keys = harness.run_keys(SEED)
    data = _data(cell)
    state = ref.init_state(kind, cfg, 1, keys["init"])
    B = mix["local_batch"]
    xs, ys = data["train_x"][:1, :B], data["train_y"][:1, :B]
    ckey = jax.random.fold_in(keys["cotrain"], 5)
    new, metrics = p.trainer._local_round_keyed(state, xs, ys, ckey[None])
    hp = harness.reference_hp(cfg, mix)
    sigma = harness.reference_sigma(cfg, mix) if noise else 0.0
    old = _client(cell, state)
    pr, px, losses = ref._client_steps(kind, cfg, hp, old["private"],
                                       old["proxy"], xs[0], ys[0], ckey,
                                       sigma, ref.HIGHEST)
    np.testing.assert_allclose(
        [float(metrics["private_loss"][0]), float(metrics["proxy_loss"][0])],
        np.asarray(losses), rtol=REL)
    got = _client(cell, new)
    for model, want in (("private", pr), ("proxy", px)):
        for k in want:
            d_got = got[model][k] - old[model][k]
            d_want = want[k] - old[model][k]
            gap = float(jnp.linalg.norm(d_got - d_want))
            assert gap <= 1e-4 * float(jnp.linalg.norm(d_want)) + 1e-9, \
                (model, k, gap)


def test_sequence_norms_match_per_example_gradients(cell):
    """Each sequence's squared norm of its proxy-loss gradient (Eq. 8), as
    the per-layer route leaves it on its probe, against the reference's
    per-example gradient by ``vmap(grad)``."""
    kind, cfg = cell["kind"], cell["cfg"]
    p = _program(cell, cfg)
    key = jax.random.PRNGKey(4)
    private = _perturbed(kind, cfg, key)
    proxy = _perturbed(kind, cfg, jax.random.fold_in(key, 9))
    rows = _data(cell)["train_x"][0]
    alpha = cfg["p4"]["alpha"]

    def one(x):
        tgt = kind.apply(cfg, private, x[None], ref.HIGHEST)
        g = jax.grad(lambda w: kind.mutual_loss(
            kind.apply(cfg, w, x[None], ref.HIGHEST), tgt, None, alpha))(proxy)
        return sum(jnp.sum(jnp.square(v)) for v in g.values())
    want = jax.vmap(one)(rows)

    t = kind.token_ids(rows)
    x, y = t[:, :-1], t[:, 1:]
    tr = p.trainer
    private_logits = tr.apply_fn(private, x)
    logits, vjp = jax.vjp(lambda w, z: tr.apply_fn.layer_forward(w, x, z),
                          proxy, jnp.zeros((x.shape[0],)))
    _, sq = vjp(tr._proxy_logit_grads(logits, private_logits, y))
    np.testing.assert_allclose(sq, want, rtol=1e-4)


@pytest.mark.parametrize("chunk", [2, 1], ids=["one-block", "blocks"])
def test_evaluation_counts_the_reference_next_tokens(cell, chunk):
    """The program's evaluation, in one block of test sequences and one
    sequence at a time, counts the positions the reference counts."""
    kind, cfg = cell["kind"], dict(cell["cfg"])
    cfg["dp"] = dict(cfg["dp"], per_example_chunk=chunk)
    p = _program(cell, cfg)
    data = _data(cell)
    state = ref.init_state(kind, cfg, 2, jax.random.PRNGKey(6))
    # an untrained model predicts few next tokens: train its head toward
    # the most frequent id so that some positions are right
    state["private"]["head"] = state["private"]["head"].at[:, :, 0].add(0.5)
    tx, ty = data["test_x"][:2], data["test_y"][:2]
    acc = p.trainer.evaluate(state, tx, ty)
    got = kind.run_correct(cfg, acc, ty)
    want = kind.correct_counts(cfg, state["private"], tx, ty)
    np.testing.assert_array_equal(got, want)
    assert want.sum() > 0


def test_token_rows_hold_ids_exactly_in_bfloat16(cell):
    """The kind's rows carry each id as two base-256 digits, which the
    reference's bfloat16 control reads back exactly; the program reads the
    same ids through ``token_rows``."""
    kind = cell["kind"]
    ids = jnp.array([[0, 255, 256, 12287, 65535]])
    rows = jnp.stack([ids // 256, ids % 256], axis=1)
    for dt in (jnp.float32, jnp.bfloat16):
        np.testing.assert_array_equal(kind.token_ids(rows.astype(dt)), ids)
    assert kind.trainer_kwargs(cell["cfg"])["token_rows"] is kind.token_ids


def test_mellum2_hand_counts():
    """Mellum2's share at 8,192 tokens: per token and layer the q, k, v, o
    projections (2304 x 128 x (2·32 + 2·4)), the router over 64 experts and
    one held expert's three products on average (top 8 of 64, 8 held); the
    head over 12,288 rows; attention's score and value products over the
    keys a query reads: every earlier one on the full layer, 1,024 on the
    three sliding ones."""
    from chipbench import counts
    cfg = harness.load_cell("mellum2-ep8.m1-s8k-b4")["cfg"]
    s = 8192
    per_layer = 2304 * 128 * 72 + 2304 * 64 + 3 * 2304 * 896
    full = 2 * 32 * 128 * s * (s + 1) // 2
    sliding = 2 * 32 * 128 * (1024 * 1025 // 2 + (s - 1024) * 1024)
    want = s * (4 * per_layer + 2304 * 12288) + full + 3 * sliding
    assert counts.model(cfg).forward_macs(cfg) == want == 1_603_679_551_488
    # about 196 M multiply-adds a token, 277 MFLOP of them matrix products
    assert want // s == 195_761_664
    assert 2 * (4 * per_layer + 2304 * 12288) == 277_217_280
    assert counts.step_flops_per_example(cfg) == 12 * want
