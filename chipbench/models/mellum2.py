"""Mellum2-12B-A2.5B (JetBrains; config ``chipbench/configs/
mellum2-12b-a2.5b-ep8.json``) as one chip's share: a decoder of periods of
three sliding-window layers and one full layer, every feed-forward a
mixture of experts of which this chip holds a contiguous share, over a
slice of the vocabulary.

The block, with RMSNorm(x) = x / sqrt(mean(x²) + eps) · scale:

    h = x + W_o · Attn(RoPE(W_q x̃), RoPE(W_k x̃), W_v x̃),   x̃ = RMSNorm(x)
    y = h + Σ_{e ∈ top-8 ∩ held} w_e · W_down,e(silu(W_gate,e h̃) ⊙ W_up,e h̃)

with h̃ = RMSNorm(h), causal GQA attention (32 query heads, 4 key/value
heads of 128) within ``sliding_window`` positions on sliding layers and
over every earlier position on full ones, and w = softmax(h̃·W_r) over all
of the published experts, renormalized over the top 8. Sliding layers take
default RoPE at their θ, full layers YaRN (arXiv:2309.00071; ``_yarn``
below, as HF's ``rope_parameters`` define it): frequencies blended between
interpolated and extrapolated by a ramp, cos and sin scaled by
``attention_factor``. The output head is untied.

The reference computes the held experts as a dense weighted sum over every
token (each held expert on every token, weight 0 where it is not chosen):
exact and dropless by construction. The layers are scanned, their type's
RoPE and window given as data; attention runs in blocks of queries, each
layer and each block under ``jax.checkpoint``.

Data: token ids drawn i.i.d. from Zipf(``traffic["tokens"]["zipf"]``) over
the vocabulary slice (code tokens are heavy-tailed, which also loads the
experts unevenly). A row of ``train_x`` is one sequence of ``seq_len + 1``
tokens: the model reads the first ``seq_len``, and the label of each
position is its next token, so a row carries its own labels and
``train_y`` (one 0 a row) none. The reference takes its arithmetic's dtype
from the rows and casts floating rows to it, so a row is float32, (2,
seq_len + 1): each id as its two base-256 digits, (id // 256, id % 256),
which bfloat16 holds exactly as float32 does. The program's language
model reads int32 token rows, and ``trainer_kwargs`` hands it
``token_ids`` to read these (its ``token_rows``). ``apply`` returns the
logits with the next tokens they predict, ``{"logits", "next"}``, which is
what ``mutual_loss`` reads. One example is one sequence; a loss is a mean
over tokens, then over sequences.
"""
from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # queries per attention block of the reference


# ---------------------------------------------------------------- sizes

def _kind(t):
    return {"sliding_attention": "sliding", "full_attention": "full"}[t]


def _published_experts(cfg):
    return cfg["deployment"]["num_experts_published"]


def trainer_kwargs(cfg):
    rope = cfg["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    # the program's layers share one θ and renormalize the top-k gates
    assert full["rope_theta"] == sliding["rope_theta"]
    assert cfg["norm_topk_prob"]
    first = cfg["deployment"]["held_experts"][0]
    return {"feat_dim": cfg["seq_len"], "num_classes": cfg["vocab_size"],
            "model": "lm", "token_rows": token_ids, "lm": {
                "num_layers": cfg["num_hidden_layers"],
                "d_model": cfg["hidden_size"],
                "num_heads": cfg["num_attention_heads"],
                "num_kv_heads": cfg["num_key_value_heads"],
                "head_dim": cfg["head_dim"],
                "d_ff": cfg["moe_intermediate_size"],
                "vocab_size": cfg["vocab_size"],
                "max_seq_len": cfg["seq_len"],
                "window": cfg["sliding_window"],
                "layer_pattern": [_kind(t) for t in cfg["layer_types"]],
                "rope_theta": float(full["rope_theta"]),
                "norm_eps": cfg["rms_norm_eps"],
                "yarn": {"factor": float(full["factor"]),
                         "original_max_position":
                             full["original_max_position_embeddings"],
                         "beta_fast": float(full["beta_fast"]),
                         "beta_slow": float(full["beta_slow"]),
                         "attention_factor": float(full["attention_factor"])},
                "moe": {"num_experts": _published_experts(cfg),
                        "experts_per_token": cfg["num_experts_per_tok"],
                        "held_first": first,
                        "held_count": cfg["num_experts"]}}}


def param_shapes(cfg):
    d, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    hd, f, held = cfg["head_dim"], cfg["moe_intermediate_size"], cfg["num_experts"]
    hq, kvh = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return {"embed": (V, d), "head": (d, V), "final_norm": (d,),
            "attn_norm": (L, d), "wq": (L, d, hq), "wk": (L, d, kvh),
            "wv": (L, d, kvh), "wo": (L, hq, d), "moe_norm": (L, d),
            "router": (L, d, _published_experts(cfg)),
            "w_gate": (L, held, d, f), "w_up": (L, held, d, f),
            "w_down": (L, held, f, d)}


def init_model(cfg, key):
    """Each weight normal / sqrt(fan-in) (its input width, the second to
    last axis) from its own fold of ``key`` in sorted key order; the
    embedding N(0, 1) (its input is one row per token); norm scales 1."""
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.float32)
            continue
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = z if name == "embed" else z / math.sqrt(shape[-2])
    return out


def shrink(cfg, mix):
    """A CPU test size with every mechanism kept: a period of one sliding
    and one full layer, YaRN, a share of 4 of 16 experts with top 4, a
    window shorter than the sequence."""
    cfg.update(num_hidden_layers=2,
               layer_types=["sliding_attention", "full_attention"],
               hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
               head_dim=8, moe_intermediate_size=16, num_experts=4,
               num_experts_per_tok=4, vocab_size=64, sliding_window=4,
               seq_len=16, reference_example_block=2)
    cfg["deployment"] = dict(cfg["deployment"], num_experts_published=16)
    cfg["dp"]["per_example_chunk"] = 2
    # eight clients of ten sequences: two groups of four, at a fraction of
    # the CPU time of the paper models' sixteen clients
    mix.update(clients=8, samples_per_client=10, train_per_client=8,
               local_batch=4)


# ---------------------------------------------------------------- data

@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _tokens(M, R, s, V, a, key):
    w = (jnp.arange(V, dtype=jnp.float32) + 1.0) ** -a
    cdf = jnp.cumsum(w) / jnp.sum(w)
    u = jax.random.uniform(key, (M, R, s + 1))
    return jnp.minimum(jnp.searchsorted(cdf, u), V - 1).astype(jnp.int32)


def make_data(traffic, cfg, key, seed):
    """train_x (M, n, 2, s + 1) and test_x (M, R - n, 2, s + 1) token rows,
    with train_y (M, n) and test_y (M, R - n) zeros, on the device."""
    M, R = traffic["clients"], traffic["samples_per_client"]
    n = traffic["train_per_client"]
    t = _tokens(M, R, cfg["seq_len"], cfg["vocab_size"],
                jnp.float32(traffic["tokens"]["zipf"]), key)
    x = jnp.stack([t // 256, t % 256], axis=2).astype(jnp.float32)
    y = jnp.zeros((M, R), jnp.int32)
    out = {"train_x": x[:, :n], "train_y": y[:, :n],
           "test_x": x[:, n:], "test_y": y[:, n:]}
    jax.block_until_ready(out)
    return out


# ---------------------------------------------------------------- model

def _yarn(head_dim, theta, p):
    """YaRN's inverse frequencies (head_dim/2,) and cos/sin scale, from HF's
    ``rope_parameters`` (``_compute_yarn_parameters``, truncate on)."""
    def corr(rot):
        return (head_dim * math.log(p["original_max_position_embeddings"]
                                    / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr(p["beta_fast"])), 0)
    high = min(math.ceil(corr(p["beta_slow"])), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    base = theta ** (np.arange(0, head_dim, 2).astype(np.float32) / head_dim)
    keep = 1 - ramp                  # share of the extrapolated frequency
    inv = 1 / (p["factor"] * base) * (1 - keep) + 1 / base * keep
    return inv.astype(np.float32), p["attention_factor"]


def _rope(t, inv, scale, dt):
    """t (b, s, heads, hd) rotated by position × inv, halves rotated
    together (HF's rotate_half), cos and sin times ``scale``."""
    s = t.shape[1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None]
    cos = (jnp.cos(ang) * scale).astype(dt)[None, :, None, :]
    sin = (jnp.sin(ang) * scale).astype(dt)[None, :, None, :]
    a, b = jnp.split(t, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + jnp.asarray(eps, x.dtype)) * scale


def _attention(q, k, v, window, prec):
    """Causal GQA attention, queries in blocks of ``Q_BLOCK`` under
    ``jax.checkpoint``, each query reading the ``window`` latest positions
    (a full layer's window is the sequence)."""
    b, s, hq, hd = q.shape
    kvh = k.shape[2]
    Q = min(Q_BLOCK, s)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def one(i, qb):
        qb = qb.reshape(b, Q, kvh, hq // kvh, hd)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k, precision=prec)
        sc = sc / jnp.sqrt(jnp.asarray(hd, sc.dtype))
        qpos = i * Q + jnp.arange(Q)
        ok = ((kpos[None, :] <= qpos[:, None])
              & (kpos[None, :] > qpos[:, None] - window))
        sc = jnp.where(ok, sc, jnp.asarray(-1e30, jnp.float32).astype(sc.dtype))
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v, precision=prec)
        return o.reshape(b, Q, hq, hd)
    qs = q.reshape(b, s // Q, Q, hq, hd).swapaxes(0, 1)
    out = jax.lax.map(lambda a: one(*a), (jnp.arange(s // Q), qs))
    return out.swapaxes(0, 1).reshape(b, s, hq, hd)


def _experts(cfg, lp, h, prec):
    """The held experts' part: every held expert on every token, weighted
    by its renormalized top-k gate (0 where the token did not choose it)."""
    k, first = cfg["num_experts_per_tok"], cfg["deployment"]["held_experts"][0]
    held = cfg["num_experts"]
    logits = jnp.einsum("bsd,de->bse", h, lp["router"], precision=prec)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    w = jnp.sum(jnp.where(ids[..., None] == first + jnp.arange(held),
                          gate[..., None], 0), axis=-2)        # (b, s, held)
    g = jnp.einsum("bsd,edf->besf", h, lp["w_gate"], precision=prec)
    u = jnp.einsum("bsd,edf->besf", h, lp["w_up"], precision=prec)
    y = jnp.einsum("besf,efd->besd", jax.nn.silu(g) * u, lp["w_down"],
                   precision=prec)
    return jnp.einsum("bse,besd->bsd", w.astype(y.dtype), y, precision=prec)


def token_ids(x):
    """Token ids (b, s + 1), int32, of rows x (b, 2, s + 1)."""
    return x[:, 0].astype(jnp.int32) * 256 + x[:, 1].astype(jnp.int32)


def apply(cfg, params, x, prec):
    """{"logits": (b, s, V), "next": (b, s)} of token rows x (b, 2, s + 1):
    the logits at the first s positions and the next token of each."""
    t = token_ids(x)
    return {"logits": _logits(cfg, params, t[:, :-1], prec), "next": t[:, 1:]}


def _layer_rope(cfg, t, s):
    """A layer type's (inverse frequencies, cos/sin scale, window)."""
    hd, rope = cfg["head_dim"], cfg["rope_parameters"]
    if _kind(t) == "full":
        inv, scale = _yarn(hd, rope["full_attention"]["rope_theta"],
                           rope["full_attention"])
        return inv, scale, s
    theta = rope["sliding_attention"]["rope_theta"]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    return inv, 1.0, cfg["sliding_window"]


def _logits(cfg, params, x, prec):
    """Logits (b, s, V) of tokens x (b, s): the layers scanned, each under
    ``jax.checkpoint``, with its type's RoPE and window as data."""
    dt = params["embed"].dtype
    eps = cfg["rms_norm_eps"]
    hd, hq = cfg["head_dim"], cfg["num_attention_heads"]
    kvh = cfg["num_key_value_heads"]
    b, s = x.shape
    per_type = [_layer_rope(cfg, t, s) for t in cfg["layer_types"]]
    inv = jnp.asarray(np.stack([r[0] for r in per_type]))
    scale = jnp.asarray([r[1] for r in per_type], jnp.float32)
    window = jnp.asarray([r[2] for r in per_type], jnp.int32)
    layers = {k: v for k, v in params.items()
              if k not in ("embed", "head", "final_norm")}

    @jax.checkpoint
    def layer(h, xs):
        lp, inv, scale, window = xs
        xn = _rmsnorm(h, lp["attn_norm"], eps)
        q = jnp.einsum("bsd,dk->bsk", xn, lp["wq"], precision=prec)
        k = jnp.einsum("bsd,dk->bsk", xn, lp["wk"], precision=prec)
        v = jnp.einsum("bsd,dk->bsk", xn, lp["wv"], precision=prec)
        q = _rope(q.reshape(b, s, hq, hd), inv, scale, dt)
        k = _rope(k.reshape(b, s, kvh, hd), inv, scale, dt)
        o = _attention(q, k, v.reshape(b, s, kvh, hd), window, prec)
        h = h + jnp.einsum("bsk,kd->bsd", o.reshape(b, s, hq * hd),
                           lp["wo"], precision=prec)
        return h + _experts(cfg, lp, _rmsnorm(h, lp["moe_norm"], eps),
                            prec), None
    h = jnp.take(params["embed"], x, axis=0)
    h, _ = jax.lax.scan(layer, h, (layers, inv, scale, window))
    h = _rmsnorm(h, params["final_norm"], eps)
    return jnp.einsum("bsd,dv->bsv", h, params["head"], precision=prec)


def _ce(logits, y):
    lp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, y[..., None], axis=-1))


def _kl(p_logits, q_logits):
    p = jax.nn.log_softmax(p_logits, axis=-1)
    q = jax.nn.log_softmax(q_logits, axis=-1)
    return jnp.mean(jnp.sum(jnp.exp(p) * (p - q), axis=-1))


def mutual_loss(out, other_out, y, weight):
    """Eqs. 8-9 per token, (1 - a)·CE(f, next) + a·KL(f ‖ g), g held
    constant, on ``apply``'s outputs (``y`` carries nothing); a mean over
    tokens, then over sequences (all of one length)."""
    other = jax.lax.stop_gradient(other_out["logits"])
    logits = out["logits"]
    return ((1.0 - weight) * _ce(logits, out["next"])
            + weight * _kl(logits, other))


# ---------------------------------------------------------------- evaluation

@partial(jax.jit, static_argnums=(0,))
def _correct(cfg_json, private, test_x, test_y):
    cfg = json.loads(cfg_json)
    dt = jax.tree_util.tree_leaves(private)[0].dtype
    prec = HIGHEST if dt == jnp.float32 else None

    def client(a):
        p, xs, ys = a

        def seq(row):
            t = token_ids(row[None])
            pred = jnp.argmax(_logits(cfg, p, t[:, :-1], prec), -1)
            return jnp.sum(pred == t[:, 1:])
        return jnp.sum(jax.lax.map(seq, xs))
    return jax.lax.map(client, (private, test_x, test_y))


def correct_counts(cfg, private, test_x, test_y):
    """Per client, the test positions whose argmax is their next token."""
    return np.asarray(_correct(json.dumps(cfg, sort_keys=True), private,
                               test_x, test_y))


def run_correct(cfg, acc, test_y):
    """The program's share of right next tokens, as a count."""
    return np.rint(np.asarray(acc, np.float64)
                   * (test_y.shape[1] * cfg["seq_len"]))
