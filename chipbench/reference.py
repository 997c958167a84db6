"""Plain reference of P4 co-training (arXiv:2405.17697, Eqs. 3-12).

Written from the paper and the configuration alone; it imports nothing of
the program under test. Each step is the literal definition: per-example
gradients by ``vmap(grad)``, per-example clipping to the global norm C
(Eq. 10), the clipped mean plus Gaussian noise (2C/n)·σ·N(0, I) (Eq. 11),
σ from Eq. 12, plain SGD, and the group mean of the proxy models. The
model itself (its parameters, forward pass and loss) is the configuration's
model kind, ``models/<model>.py``, which imports nothing of the program
either.

Two conventions are shared with the system under test because they are part
of the run's inputs, not of its arithmetic:

* random streams: round r uses ``rk = fold_in(phase_key, r)``; stream 0
  draws the batch indices (``randint`` over the local rows, with
  replacement), stream 1 is split into one key per client, and local step k
  of a client uses ``fold_in(client_key, k)`` as its noise key; a sampled
  schedule draws its cohort from stream 3;
* the noise vector is drawn once per client step as a flat (D,) float32
  normal and laid over the parameters in sorted key order, each leaf
  raveled row-major.

``dtype=jnp.bfloat16`` runs the whole reference in bfloat16 (parameters,
data, activations, updates): the lower-precision control.

Memory: a round holds one state and one block of examples. ``_round``
takes its state donated and writes each block of ``reference_block``
clients back into that buffer; within a client step the per-example work
(the clipped per-example sum, the private gradient and the losses) runs in
blocks of the configuration's ``reference_example_block`` examples, each
sum gathered in float32 accumulators of one model's size. Without that key
the batch is one block: the (n, D) stack of per-example gradients, with
the arithmetic of the unblocked reference. Besides the state and the
block, a round keeps temporaries of one model's size (the gradients, the
flat noise, XLA's layout copies): the contract bounds the state, not them.
"""
from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------- models
#
# ``kind`` is the configuration's model kind (``models/<model>.py``): it
# gives one model's parameters, forward pass and loss. Jitted functions take
# it and the configuration as static arguments, the configuration as its
# JSON text.

def _static(cfg):
    return json.dumps(cfg, sort_keys=True)


def as_dtype(t, dtype):
    """Floating inputs in ``dtype``; token ids and labels as they are."""
    return t.astype(dtype) if jnp.issubdtype(t.dtype, jnp.floating) else t


@partial(jax.jit, static_argnums=(0, 1, 2))
def _init_state(kind, cfg_json, M, key):
    cfg = json.loads(cfg_json)

    def stack(p):
        return jax.tree_util.tree_map(
            lambda t: jnp.broadcast_to(t[None], (M,) + t.shape), p)
    return {"private": stack(kind.init_model(cfg, jax.random.fold_in(key, 0))),
            "proxy": stack(kind.init_model(cfg, jax.random.fold_in(key, 1)))}


def init_state(kind, cfg, M: int, key):
    """One initialization shared by every client (private and proxy models
    drawn apart), stacked over M clients, made on the device in one call."""
    return _init_state(kind, _static(cfg), M, key)


def noble_sigma(epsilon, delta, sample_rate, rounds, local_steps):
    """Eq. 12 with l = M' = 1 (the P2P setting)."""
    s, T, K = sample_rate, rounds, local_steps
    return float(s * math.sqrt(T * K * math.log(2 * T / delta)
                               * math.log(2 / delta)) / epsilon)


# ---------------------------------------------------------------- one client

def _flat(tree):
    return jnp.concatenate([jnp.ravel(tree[k]) for k in sorted(tree)])


def _unflat(vec, like):
    out, off = {}, 0
    for k in sorted(like):
        n = like[k].size
        out[k] = vec[off:off + n].reshape(like[k].shape)
        off += n
    return out


def _in_blocks(f, x, y, b, weigh):
    """``f`` over the batch (x, y) in blocks of ``b`` examples. Where one
    block holds the batch, ``f(x, y)`` itself; else the sum of the blocks'
    values in float32, each weighted by b / n where ``weigh`` (block means
    of a batch mean), cast back to ``f``'s dtypes."""
    n = x.shape[0]
    if b >= n:
        return f(x, y)
    if n % b:
        raise ValueError(f"reference_example_block {b} does not divide the "
                         f"batch of {n} examples")
    xs, ys = (t.reshape((n // b, b) + t.shape[1:]) for t in (x, y))
    like = jax.eval_shape(f, xs[0], ys[0])
    w = b / n if weigh else 1.0

    def add(acc, xy):
        return jax.tree_util.tree_map(
            lambda a, v: a + v.astype(jnp.float32) * w, acc, f(*xy)), None
    acc, _ = jax.lax.scan(add, jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, jnp.float32), like), (xs, ys))
    return jax.tree_util.tree_map(lambda a, s: a.astype(s.dtype), acc, like)


def _client_steps(kind, cfg, hp, private, proxy, x, y, ckey, sigma, prec,
                  half_batch=False):
    """K local steps of one client, then its losses at the updated models.
    ``half_batch`` plants a fault: the step uses half of the batch and takes
    the mean over that half."""
    dt = x.dtype
    apply, mutual_loss = kind.apply, kind.mutual_loss
    lr, clip = hp["lr"], hp["clip"]
    if half_batch:
        x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
    n = x.shape[0]
    b = cfg.get("reference_example_block", n)
    noise_scale = (jnp.float32(2.0 * clip / n) * jnp.float32(sigma))

    def step(carry, k):
        pr, px = carry

        def private_grad(xb, yb):
            px_logits = apply(cfg, px, xb, prec)
            return jax.grad(lambda th: mutual_loss(
                apply(cfg, th, xb, prec), px_logits, yb, hp["beta"]))(pr)

        def clipped(xi, yi):
            """One example's proxy gradient and its clip scale (Eq. 10)."""
            tgt = apply(cfg, pr, xi[None], prec)
            g = jax.grad(lambda w: mutual_loss(apply(cfg, w, xi[None], prec),
                                               tgt, yi[None],
                                               hp["alpha"]))(px)
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(v)) for v in g.values()))
            scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
            return g, scale.astype(dt)

        def clipped_sum(xb, yb):
            g, scale = jax.vmap(clipped)(xb, yb)
            return jax.tree_util.tree_map(lambda t: jnp.sum(
                t * scale.reshape((-1,) + (1,) * (t.ndim - 1)), axis=0), g)

        g_pr = _in_blocks(private_grad, x, y, b, weigh=True)
        noise = jax.random.normal(jax.random.fold_in(ckey, k),
                                  (sum(v.size for v in px.values()),),
                                  jnp.float32)
        if b >= n:
            # The batch in one block: the flat (n, D) stack, summed at once,
            # as the unblocked reference did (a sum per leaf of the same
            # values rounds differently on the CPU).
            def one(xi, yi):
                g, scale = clipped(xi, yi)
                return _flat(g) * scale

            per_ex = jax.vmap(one)(x, y)
            mean = jnp.sum(per_ex, axis=0) / jnp.asarray(n, dt)
            g_px = _unflat(mean + (noise_scale * noise).astype(dt), px)
        else:
            total = _in_blocks(clipped_sum, x, y, b, weigh=False)
            g_px = jax.tree_util.tree_map(
                lambda t, z: t / jnp.asarray(n, dt)
                + (noise_scale * z).astype(dt), total, _unflat(noise, px))
        pr = jax.tree_util.tree_map(lambda p, g: p - (lr * g).astype(dt),
                                    pr, g_pr)
        px = jax.tree_util.tree_map(lambda p, g: p - (lr * g).astype(dt),
                                    px, g_px)
        return (pr, px), None

    (private, proxy), _ = jax.lax.scan(step, (private, proxy),
                                       jnp.arange(hp["local_steps"]))

    def losses(xb, yb):
        pr_logits = apply(cfg, private, xb, prec)
        px_logits = apply(cfg, proxy, xb, prec)
        return jnp.stack([mutual_loss(pr_logits, px_logits, yb, hp["beta"]),
                          mutual_loss(px_logits, pr_logits, yb,
                                      hp["alpha"])])
    return private, proxy, _in_blocks(losses, x, y, b,
                                      weigh=True).astype(jnp.float32)


# ---------------------------------------------------------------- one round

def _group_mean(tree, ids, num_groups, mask):
    counts = jax.ops.segment_sum(mask, ids, num_groups)

    def f(x):
        w = mask.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
        sums = jax.ops.segment_sum(x * w, ids, num_groups)
        denom = jnp.maximum(counts, 1.0).reshape((-1,) + (1,) * (x.ndim - 1))
        mean = (sums / denom.astype(x.dtype))[ids]
        return jnp.where(w > 0, mean, x)
    return jax.tree_util.tree_map(f, tree)


def _cohort(schedule, key, M):
    kind = schedule.get("kind", "full")
    if kind == "full":
        return jnp.ones((M,), jnp.float32)
    if kind != "sampling":
        raise ValueError(f"reference has no schedule {kind!r}")
    k1, _ = jax.random.split(key)
    q = schedule["client_rate"]
    u = jax.random.uniform(k1, (M,))
    if schedule.get("mode", "bernoulli") == "fixed":
        _, idx = jax.lax.top_k(-u, max(1, int(round(q * M))))
        return jnp.zeros((M,), jnp.float32).at[idx].set(1.0)
    return (u < q).astype(jnp.float32)


def _rows_at(t, idx):
    """Rows ``idx`` (clients, batch) of each client's ``t`` (clients, rows,
    ...)."""
    return jnp.take_along_axis(t, idx.reshape(idx.shape + (1,) * (t.ndim - 2)),
                               axis=1)


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6), donate_argnums=(7,))
def _round(kind, cfg_json, hp_items, schedule_items, batch, block, fault,
           state, train_x, train_y, phase_key, r, sigma, ids, num_groups_arr):
    """One round on ``state``, donated: each block of ``block`` clients is
    read from it and its new rows written back in place (a client outside
    the cohort keeps its rows), then the group mean of the proxies."""
    cfg, hp, schedule = (json.loads(cfg_json), dict(hp_items),
                         dict(schedule_items))
    dt = jax.tree_util.tree_leaves(state)[0].dtype
    prec = HIGHEST if dt == jnp.float32 else None
    M, R = train_y.shape
    rk = jax.random.fold_in(phase_key, r)
    if batch:
        idx = jax.random.randint(jax.random.fold_in(rk, 0), (M, batch), 0, R)
    else:
        idx = jnp.broadcast_to(jnp.arange(R), (M, R))
    keys = jax.random.split(jax.random.fold_in(rk, 1), M)
    mask = _cohort(schedule, jax.random.fold_in(rk, 3), M)

    def one_block(state, i):
        def rows(t):
            return jax.lax.dynamic_slice_in_dim(t, i * block, block)

        def put(t, new):
            keep = rows(mask).reshape((-1,) + (1,) * (new.ndim - 1))
            return jax.lax.dynamic_update_slice_in_dim(
                t, jnp.where(keep > 0, new, rows(t)), i * block, 0)
        pr, px = (jax.tree_util.tree_map(rows, state[m])
                  for m in ("private", "proxy"))
        ib, kb = rows(idx), rows(keys)
        xs = as_dtype(_rows_at(rows(train_x), ib), dt)
        ys = _rows_at(rows(train_y), ib)
        pr, px, lb = jax.vmap(lambda p, q, x, y, k: _client_steps(
            kind, cfg, hp, p, q, x, y, k, sigma, prec,
            half_batch=(fault == "half_batch")))(pr, px, xs, ys, kb)
        return {m: jax.tree_util.tree_map(put, state[m], new)
                for m, new in (("private", pr), ("proxy", px))}, lb

    state, losses = jax.lax.scan(one_block, state, jnp.arange(M // block))
    losses = losses.reshape(M, 2)
    if ids is not None:
        state = {"private": state["private"],
                 "proxy": _group_mean(state["proxy"], ids,
                                      num_groups_arr.shape[0], mask)}
    return state, jnp.mean(losses, axis=0)


def run_rounds(kind, cfg, hp, schedule, state, data, phase_key, start, stop,
               batch, sigma, groups=None, block=8, fault=None):
    """Rounds [start, stop) of the reference on one buffer: ``state`` is
    donated (the caller's arrays are consumed). Returns (state, losses) with
    losses (rounds, 2): the mean private and proxy loss of each round."""
    ids = G = None
    if groups is not None:
        ids_np = np.zeros((data["train_y"].shape[0],), np.int32)
        for gi, g in enumerate(groups):
            ids_np[list(g)] = gi
        ids, G = jnp.asarray(ids_np), jnp.zeros((len(groups),))
    out = []
    for r in range(start, stop):
        state, losses = _round(
            kind, _static(cfg), tuple(sorted(hp.items())),
            tuple(sorted(schedule.items())), batch, block, fault, state,
            data["train_x"], data["train_y"], phase_key, r,
            jnp.float32(sigma), ids, G)
        out.append(losses)
    return state, np.asarray(jnp.stack(out), np.float64)


# ---------------------------------------------------------------- Phase 1

@jax.jit
def _l1(proxy):
    w = jax.vmap(_flat)(proxy)
    return jax.lax.map(lambda row: jnp.sum(jnp.abs(w - row[None]), axis=-1), w)


def l1_distances(proxy):
    """Eq. 3: pairwise ℓ1 distance of the flattened proxy models (M, M), in
    the models' own precision."""
    return np.asarray(_l1(proxy).astype(jnp.float32), np.float64)


def _known_peers(M, H, seed):
    rng = np.random.default_rng(seed)
    H = min(H, M - 1)
    known = np.zeros((M, M), bool)
    for i in range(M if H > 0 else 0):
        cands = [j for j in range(M) if j != i]
        known[i, rng.choice(cands, H, replace=False)] = True
    return known | known.T, rng


def greedy_groups(dist, group_size, sample_peers, seed):
    """The paper's greedy Phase-1 procedure (§3.3) on a distance matrix:
    each client sees H sampled peers (symmetric), mutual nearest pairs form
    first, every other client pairs with its nearest ungrouped peer, an odd
    leftover joins a random pair, then groups merge with their nearest
    partner group (closest member pair) while they fit in ``group_size``.
    A federation of one client forms one group, ``[[0]]``."""
    M = dist.shape[0]
    known, rng = _known_peers(M, sample_peers, seed)
    masked = np.where(known, dist, np.inf)
    np.fill_diagonal(masked, np.inf)

    best = np.argmin(masked, axis=1)
    ungrouped, out = set(range(M)), []
    for i in range(M):
        j = int(best[i])
        if i < j and best[j] == i and i in ungrouped and j in ungrouped:
            out.append([i, j])
            ungrouped -= {i, j}
    for i in sorted(ungrouped):
        if i not in ungrouped:
            continue
        cands = [j for j in sorted(ungrouped) if j != i]
        if not cands:
            break
        j = cands[int(np.argmin([masked[i, j] for j in cands]))]
        if not np.isfinite(masked[i, j]):
            j = int(rng.choice(cands))
        out.append([i, j])
        ungrouped -= {i, j}
    for i in sorted(ungrouped):
        if not out:             # M = 1: one client forms one group
            out.append([i])
            continue
        out[rng.integers(len(out))].append(i)

    def gdist(a, b):
        vals = [masked[i, j] for i in a for j in b if np.isfinite(masked[i, j])]
        return min(vals) if vals else np.inf

    while True:
        merged = False
        for g in [g for g in out if len(g) < group_size]:
            if g not in out:
                continue
            partners = [h for h in out
                        if h is not g and len(h) + len(g) <= group_size]
            if not partners:
                continue
            finite = [h for h in partners if np.isfinite(gdist(g, h))]
            if finite:
                h = finite[int(np.argmin([gdist(g, h) for h in finite]))]
            else:
                h = partners[rng.integers(len(partners))]
            out.remove(g)
            out.remove(h)
            out.append(sorted(g + h))
            merged = True
        if not merged:
            break
    return [sorted(g) for g in out]
