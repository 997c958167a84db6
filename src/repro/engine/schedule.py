"""Round schedules: who participates in a round and how updates merge.

The PR-2 engine ran one hardwired round body — every client trains every
round, synchronous aggregation. A ``RoundSchedule`` owns that body instead,
so partial participation and async aggregation are engine features (one
schedule object) rather than per-strategy rewrites:

  FullParticipation  — the PR-2 body, verbatim. Bit-identical trajectories
                       (locked down in ``tests/test_engine.py``).
  ClientSampling     — Bernoulli-q or fixed-size cohorts drawn with
                       ``jax.random`` *inside* the jitted chunk; the scan
                       stays device-resident and the per-round participation
                       masks come back as a stacked scan output, so host-side
                       byte accounting and the privacy ledger see the exact
                       cohorts the device drew.
  AsyncStaleness     — buffered aggregation: clients train every round but
                       the merge runs only every ``staleness + 1`` rounds,
                       discounted by the FedBuff-style polynomial staleness
                       weight (1 + s)^(-staleness_pow). staleness=0 is the
                       synchronous body exactly.

Each schedule writes ONE round body, against a layout context
(``LayoutCtx``): the engine hands it ``ResidentCtx`` (``Engine``),
``ClientShardCtx`` (``ShardedEngine``) or ``PagedCtx`` (``PagedEngine``),
and the context draws the batches, takes its rows of a full (M,) mask and
calls the strategy's hook for that layout. A new layout is one context
class; a new schedule is one body.

Per-round randomness matches the PR-2 derivation — ``rk = fold_in(phase_key,
r)`` with streams 0/1/2 for batch/local/aggregate — and ClientSampling draws
its mask from the previously unused stream 3, so adding a schedule never
perturbs the existing streams.

Participation semantics (ClientSampling): an absent client neither trains,
sends, nor receives this round — its state is bit-unchanged. Present clients
aggregate over the cohort only (strategies override ``aggregate_masked`` for
method-specific cohort math, e.g. P4's masked group mean); decentralized
methods whose aggregation reads neighbors (ring / exponential graph) see the
absent neighbor's last-known state, which is what a real stale cache holds.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.obs.layers import layer
from repro.resilience import current_faults


def _blend(agg, base, w, w_rows=None):
    """``w · agg + (1 − w) · base`` on every leaf. With ``w_rows`` (this
    layout's rows of a per-client weight vector), client-stacked leaves blend
    with their own row's weight and replicated/server leaves with ``w`` (so
    every layout agrees on non-stacked state)."""
    rows = None if w_rows is None else w_rows.shape[0]

    def f(a, b):
        ww = w
        if rows is not None and a.ndim >= 1 and a.shape[:1] == (rows,):
            ww = w_rows.reshape((rows,) + (1,) * (a.ndim - 1))
        return (ww * a + (1.0 - ww) * b).astype(b.dtype)
    return jax.tree_util.tree_map(f, agg, base)


def _unless_empty(mask, state, new):
    """``new``, or ``state`` unchanged when no client participates: an empty
    cohort's round is a no-op (stacked strategies are already frozen by the
    merges; this guards server-style states whose cohort-weighted
    aggregation has no cohort to weight)."""
    empty = jnp.sum(mask) == 0
    return jax.tree_util.tree_map(lambda s, n: jnp.where(empty, s, n),
                                  state, new)


def sample_client_batches(train_x, train_y, key, batch_size: Optional[int]):
    """Per-client minibatches drawn on device: (M, B, ...), (M, B).

    ``batch_size=None`` means full-batch (returns the stacks unchanged —
    used by P4's bootstrap phase, which trains on the whole local dataset).
    """
    if batch_size is None:
        return train_x, train_y
    with layer("sample_batches"):
        M, R = train_y.shape
        idx = jax.random.randint(key, (M, batch_size), 0, R)
        xs = jnp.take_along_axis(
            train_x, idx.reshape(idx.shape + (1,) * (train_x.ndim - 2)),
            axis=1)
        ys = jnp.take_along_axis(train_y, idx, axis=1)
    return xs, ys


class LayoutCtx:
    """How a round body sees the engine's layout of the clients.

    A schedule writes its round body once, against this protocol; each
    engine hands the body its layout:

      ``ResidentCtx``     ``Engine``: the whole (M, ...) stack in one program
      ``ClientShardCtx``  ``ShardedEngine``: this shard's rows, in shard_map
      ``PagedCtx``        ``PagedEngine``: the compact (C, ...) cohort rows

    Every layout answers:

      ``M``                        the federation's client count
      ``client_keys(key)``         this layout's rows of ``split(key, M)``
      ``sample_batches(...)``      this layout's rows of the (M, B) draw
      ``rows(v)``                  this layout's rows of a full (M,) vector
      ``metric_means(per_client)`` the scalar means the resident path records
      ``local_update``, ``aggregate``, ``aggregate_masked``,
      ``merge_participation``      the strategy's hook for this layout,
                                   entered under the ``local_update`` /
                                   ``aggregate`` layer (``repro.obs.layers``)
      ``mix(strategy, tree, ...)`` one gossip step over the strategy's plan
      ``merge_when(pred, merge, state)``  ``merge(state)`` where ``pred``

    The per-client randomness is layout-invariant: every draw is made at
    full M and sliced, so a client's stream never depends on where it sits.
    """

    def merge_participation(self, strategy, prev, new, rows):
        with layer("aggregate"):
            return strategy.merge_participation(prev, new, rows)

    def merge_when(self, pred, merge, state):
        return jax.lax.cond(pred, merge, lambda s: s, state)

    def mix(self, strategy, tree, r, key, halo=None):
        """t ← W_r t on every client-stacked leaf of ``tree``, with the
        round's link faults drawn in-jit from ``key``'s fault stream (a
        correlated fault process's realized keep matrix supersedes the
        plan's i.i.d. rates). Identity without a configured topology.
        ``halo``: boundary rows a client shard prefetched last round."""
        plan = strategy._mix_plan
        if plan is None:
            return tree
        af = current_faults()
        keep = None if af is None else af.real.keep
        return self._mix_step(tree, plan, r, key, keep, halo)


class ResidentCtx(LayoutCtx):
    """The identity layout: every client's row in one (M, ...) stack."""

    def __init__(self, num_clients: int):
        self.M = int(num_clients)

    def client_keys(self, key):
        return jax.random.split(key, self.M)

    def sample_batches(self, train_x, train_y, key, batch_size):
        return sample_client_batches(train_x, train_y, key, batch_size)

    def rows(self, v):
        return v

    def metric_means(self, per_client):
        return {k: jnp.mean(v) for k, v in per_client.items()}

    def local_update(self, strategy, state, xs, ys, r, key):
        with layer("local_update"):
            return strategy.local_update(state, xs, ys, r, key)

    def aggregate(self, strategy, state, r, key):
        with layer("aggregate"):
            return strategy.aggregate(state, r, key)

    def aggregate_masked(self, strategy, state, r, key, mask, rows):
        with layer("aggregate"):
            return strategy.aggregate_masked(state, r, key, mask)

    def _mix_step(self, tree, plan, r, key, keep, halo):
        from repro.topology.mixing import mix_stacked
        return mix_stacked(tree, plan, r, key, keep=keep)


def _masked_round(strategy, ctx, state, xs, ys, r, rk, mask):
    """The round over the (M,) participation ``mask``: absent clients
    neither train, send nor receive — their state is bit-unchanged."""
    rows = ctx.rows(mask)
    new, metrics = ctx.local_update(strategy, state, xs, ys, r,
                                    jax.random.fold_in(rk, 1))
    # absent clients' local training is discarded: aggregation sees their
    # pre-round (last-known) state
    new = ctx.merge_participation(strategy, state, new, rows)
    new = ctx.aggregate_masked(strategy, new, r, jax.random.fold_in(rk, 2),
                               mask, rows)
    # ...and they receive nothing: final state is bit-unchanged
    new = ctx.merge_participation(strategy, state, new, rows)
    return _unless_empty(mask, state, new), (metrics, {"participation": mask})


@dataclass(eq=False)  # identity hash: schedules are closed over by jitted chunks
class RoundSchedule:
    """Owns the engine's scanned round body.

    ``round_body(strategy, batch_size, ctx)`` returns
    ``body(state, r, phase_key, train_x, train_y) -> (state, (metrics, aux))``
    written against the engine's layout ``ctx`` (``LayoutCtx``), where
    ``aux`` is an (empty or participation-carrying) dict of per-round arrays
    stacked by the scan — the engine forwards ``aux["participation"]`` to
    byte accounting and History.
    """

    name = "full"

    def client_fraction(self, M: Optional[int] = None) -> float:
        """Expected fraction of clients participating per round — the
        schedule's contribution to the ledger's effective sampling rate."""
        return 1.0

    def fingerprint(self):
        """Value key for the engine's compiled-chunk cache (all schedule
        fields are trace-baked constants, so all of them key)."""
        return (type(self).__name__,) + tuple(
            (f.name, getattr(self, f.name)) for f in dataclasses.fields(self))

    def round_body(self, strategy, batch_size: Optional[int], ctx):
        raise NotImplementedError


@dataclass(eq=False)
class FullParticipation(RoundSchedule):
    """Every client, every round, synchronous aggregation — the PR-2 body."""

    name = "full"

    def round_body(self, strategy, batch_size, ctx):
        def body(state, r, phase_key, train_x, train_y):
            rk = jax.random.fold_in(phase_key, r)
            xs, ys = ctx.sample_batches(
                train_x, train_y, jax.random.fold_in(rk, 0), batch_size)
            af = current_faults()
            if af is None:
                state, metrics = ctx.local_update(
                    strategy, state, xs, ys, r, jax.random.fold_in(rk, 1))
                state = ctx.aggregate(strategy, state, r,
                                      jax.random.fold_in(rk, 2))
                return state, (metrics, {})
            # faults installed: down/slow clients are frozen — their training
            # is discarded, they receive nothing, and aggregation runs over
            # the active cohort (the ClientSampling machinery, mask = active;
            # the realization is replicated, so every layout sees the same)
            return _masked_round(strategy, ctx, state, xs, ys, r, rk,
                                 af.real.active())

        return body


@dataclass(eq=False)
class ClientSampling(RoundSchedule):
    """Partial participation: a per-round cohort drawn inside the jit.

    ``mode="bernoulli"`` — each client independently with probability q.
    This is exact Poisson sampling — the amplification-by-subsampling regime
    the RDP accountant models — so an empty draw is NOT redrawn or patched
    (that would raise the true inclusion probability above the q the ledger
    accounts at); an empty-cohort round is a no-op (state passes through
    unchanged, guarded in the round body for server-style strategies whose
    cohort-weighted aggregation would otherwise divide by zero).
    ``mode="fixed"`` — a uniformly random cohort of exactly
    ``max(1, round(q·M))`` clients (sampling without replacement).
    """

    q: float = 1.0
    mode: str = "bernoulli"          # bernoulli | fixed
    name = "sampling"

    def client_fraction(self, M: Optional[int] = None) -> float:
        if self.mode == "fixed" and M:
            return max(1, int(round(self.q * M))) / M
        return min(1.0, float(self.q))

    def draw_mask(self, key, M: int):
        """(M,) float32 0/1 participation mask; deterministic in ``key``."""
        k1, _ = jax.random.split(key)
        if self.mode == "fixed":
            k = max(1, int(round(self.q * M)))
            # top_k of the negated uniforms = the k smallest = the stable
            # argsort's first k rows (both break ties lower-index-first), so
            # the mask is bit-identical to the argsort lowering — but O(M·k)
            # instead of a full O(M log M) sort, which is what makes fixed
            # cohorts affordable per round at virtual-population M (the
            # paged engine draws and host-replays this at full M)
            u = jax.random.uniform(k1, (M,))
            _, idx = jax.lax.top_k(-u, k)
            return jnp.zeros((M,), jnp.float32).at[idx].set(1.0)
        return (jax.random.uniform(k1, (M,)) < self.q).astype(jnp.float32)

    def round_body(self, strategy, batch_size, ctx):
        def body(state, r, phase_key, train_x, train_y):
            rk = jax.random.fold_in(phase_key, r)
            xs, ys = ctx.sample_batches(
                train_x, train_y, jax.random.fold_in(rk, 0), batch_size)
            # the full (M,) mask is drawn in every layout — each takes its
            # own rows of it — and the aux output stays the full mask, so
            # byte accounting and the ledger see exactly the same cohorts
            mask = self.draw_mask(jax.random.fold_in(rk, 3), ctx.M)
            af = current_faults()
            if af is not None:
                # a sampled client that is down or slow still can't serve
                mask = mask * af.real.active()
            return _masked_round(strategy, ctx, state, xs, ys, r, rk, mask)

        return body


@dataclass(eq=False)
class AsyncStaleness(RoundSchedule):
    """Buffered aggregation: merge every ``staleness + 1`` rounds.

    Clients train every round; their unaggregated local progress is the
    buffer. At each merge point the aggregate is folded in with the
    FedBuff-style polynomial staleness discount
    ``w = (1 + staleness)^(-staleness_pow)``:

        state ← w · aggregate(state) + (1 − w) · state

    so the staler the buffered updates, the less the consensus direction is
    trusted. ``staleness=0`` reduces to the synchronous body exactly (w = 1,
    merge every round) — locked down in ``tests/test_schedule.py``.
    """

    staleness: int = 0
    staleness_pow: float = 0.5
    name = "async"

    def round_body(self, strategy, batch_size, ctx):
        period = int(self.staleness) + 1
        weight = float(period ** (-self.staleness_pow))

        def body(state, r, phase_key, train_x, train_y):
            rk = jax.random.fold_in(phase_key, r)
            xs, ys = ctx.sample_batches(
                train_x, train_y, jax.random.fold_in(rk, 0), batch_size)
            af = current_faults()
            if af is not None:
                return faulted(state, r, rk, xs, ys, af)
            state, metrics = ctx.local_update(strategy, state, xs, ys, r,
                                              jax.random.fold_in(rk, 1))
            if period == 1:   # synchronous: identical to FullParticipation
                state = ctx.aggregate(strategy, state, r,
                                      jax.random.fold_in(rk, 2))
                return state, (metrics, {})

            def merge(s):
                agg = ctx.aggregate(strategy, s, r, jax.random.fold_in(rk, 2))
                return _blend(agg, s, weight)

            state = ctx.merge_when(jnp.equal(r % period, period - 1), merge,
                                   state)
            return state, (metrics, {})

        def faulted(state, r, rk, xs, ys, af):
            # realized staleness: each client's merge weight comes from the
            # rounds it actually missed ((1+age)^-pow, FedBuff form) instead
            # of the configured scalar s — slow devices emerge from the
            # straggler chain
            active = af.real.active()
            rows = ctx.rows(active)
            new, metrics = ctx.local_update(strategy, state, xs, ys, r,
                                            jax.random.fold_in(rk, 1))
            new = ctx.merge_participation(strategy, state, new, rows)
            agg = ctx.aggregate_masked(strategy, new, r,
                                       jax.random.fold_in(rk, 2), active,
                                       rows)
            w = (1.0 + af.real.age) ** (-self.staleness_pow)
            if strategy.state_client_stacked(state):
                w_rows = ctx.rows(w)
                merged = _blend(agg, new, jnp.mean(w), w_rows)
                hold = new
            else:
                # server-style state: the aggregate folds into the previous
                # global model at the mean realized discount
                merged = _blend(agg, state, jnp.mean(w))
                hold = state
            if period > 1:
                is_merge = jnp.equal(r % period, period - 1)
                merged = jax.tree_util.tree_map(
                    lambda m, n: jnp.where(is_merge, m, n), merged, hold)
            merged = ctx.merge_participation(strategy, state, merged, rows)
            return (_unless_empty(active, state, merged),
                    (metrics, {"participation": active}))

        return body


def wrap_overlap(body, strategy, ctx):
    """Thread the strategy's prefetched halo blocks through the scan carry.

    The wrapped body's carry is ``(state, halos)``: the round's hooks trace
    with ``current_halos()`` holding the blocks exchanged at the END of the
    previous round (so the ppermute for round r's boundary rows was issued
    before round r-1's local compute finished — compute/communication
    overlap), and a fresh prefetch is issued from the new state afterwards.
    Strategies that return None from ``sharded_prefetch`` carry an empty
    tuple; their rounds trace exactly as before.
    """
    from repro.engine.strategy import sharded_halos

    def wrapped(carry, r, phase_key, *data):
        state, halos = carry
        empty = isinstance(halos, tuple) and not halos
        with sharded_halos(None if empty else halos):
            state, out = body(state, r, phase_key, *data)
        nxt = strategy.sharded_prefetch(state, ctx)
        return (state, () if nxt is None else nxt), out

    return wrapped


def make_schedule(cfg) -> RoundSchedule:
    """Build a RoundSchedule from a ``repro.config.ScheduleConfig``."""
    if cfg is None or cfg.kind == "full":
        return FullParticipation()
    if cfg.kind == "sampling":
        return ClientSampling(q=cfg.client_rate, mode=cfg.mode)
    if cfg.kind == "async":
        return AsyncStaleness(staleness=cfg.staleness,
                              staleness_pow=cfg.staleness_pow)
    raise ValueError(f"unknown schedule kind {cfg.kind!r}; "
                     "expected full | sampling | async")
