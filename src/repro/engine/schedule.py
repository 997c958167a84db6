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


def _fault_blend(agg, base, w_rows, rows: int, w_scalar):
    """Per-client staleness-discounted fold: client-stacked leaves blend with
    their own realized weight, replicated/server leaves with the mean weight
    (so single-device and sharded traces agree on non-stacked state)."""
    def f(a, b):
        if a.ndim >= 1 and a.shape[:1] == (rows,):
            ww = w_rows.reshape((rows,) + (1,) * (a.ndim - 1))
        else:
            ww = w_scalar
        return (ww * a + (1.0 - ww) * b).astype(b.dtype)
    return jax.tree_util.tree_map(f, agg, base)


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


class Scoped:
    """The strategy as every round body sees it: the engines hand
    ``Scoped(strategy)`` to the schedule, so each body's local update runs
    under the ``local_update`` layer and its aggregation, with the
    ``merge_participation`` calls around it, under ``aggregate``
    (``repro.obs.layers``). Everything else is the strategy's own."""

    def __init__(self, strategy):
        self._strategy = strategy

    def __getattr__(self, name):
        return getattr(self._strategy, name)

    def _scoped(name, layer_name):
        def call(self, *args, **kwargs):
            with layer(layer_name):
                return getattr(self._strategy, name)(*args, **kwargs)
        call.__name__ = name
        return call

    local_update = _scoped("local_update", "local_update")
    sharded_local_update = _scoped("sharded_local_update", "local_update")
    paged_local_update = _scoped("paged_local_update", "local_update")
    aggregate = _scoped("aggregate", "aggregate")
    aggregate_masked = _scoped("aggregate_masked", "aggregate")
    sharded_aggregate = _scoped("sharded_aggregate", "aggregate")
    sharded_aggregate_masked = _scoped("sharded_aggregate_masked",
                                       "aggregate")
    paged_aggregate_masked = _scoped("paged_aggregate_masked", "aggregate")
    merge_participation = _scoped("merge_participation", "aggregate")
    del _scoped


@dataclass(eq=False)  # identity hash: schedules are closed over by jitted chunks
class RoundSchedule:
    """Owns the engine's scanned round body.

    ``round_body(strategy, batch_size)`` returns
    ``body(state, r, phase_key, train_x, train_y) -> (state, (metrics, aux))``
    where ``aux`` is an (empty or participation-carrying) dict of per-round
    arrays stacked by the scan — the engine forwards ``aux["participation"]``
    to byte accounting and History.
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

    def round_body(self, strategy, batch_size: Optional[int]):
        raise NotImplementedError

    def sharded_round_body(self, strategy, batch_size: Optional[int], ctx):
        """Round body for a shard_map region over the client axis: same key
        derivation and call sequence as ``round_body``, with the strategy's
        sharded hooks in place of the single-device ones (see
        ``repro.engine.sharded``)."""
        raise NotImplementedError(
            f"{type(self).__name__} has no sharded round body")


@dataclass(eq=False)
class FullParticipation(RoundSchedule):
    """Every client, every round, synchronous aggregation — the PR-2 body."""

    name = "full"

    def round_body(self, strategy, batch_size):
        def body(state, r, phase_key, train_x, train_y):
            rk = jax.random.fold_in(phase_key, r)
            xs, ys = sample_client_batches(
                train_x, train_y, jax.random.fold_in(rk, 0), batch_size)
            af = current_faults()
            if af is None:
                state, metrics = strategy.local_update(
                    state, xs, ys, r, jax.random.fold_in(rk, 1))
                state = strategy.aggregate(state, r, jax.random.fold_in(rk, 2))
                return state, (metrics, {})
            # faults installed: down/slow clients are frozen — their training
            # is discarded, they receive nothing, and aggregation runs over
            # the active cohort (the ClientSampling machinery, mask = active)
            active = af.real.active()
            new, metrics = strategy.local_update(
                state, xs, ys, r, jax.random.fold_in(rk, 1))
            new = strategy.merge_participation(state, new, active)
            new = strategy.aggregate_masked(new, r, jax.random.fold_in(rk, 2),
                                            active)
            new = strategy.merge_participation(state, new, active)
            empty = jnp.sum(active) == 0
            state = jax.tree_util.tree_map(
                lambda s, n: jnp.where(empty, s, n), state, new)
            return state, (metrics, {"participation": active})

        return body

    def sharded_round_body(self, strategy, batch_size, ctx):
        def body(state, r, phase_key, train_x, train_y):
            rk = jax.random.fold_in(phase_key, r)
            xs, ys = ctx.sample_local_batches(
                train_x, train_y, jax.random.fold_in(rk, 0), batch_size)
            af = current_faults()
            if af is None:
                state, metrics = strategy.sharded_local_update(
                    state, xs, ys, r, jax.random.fold_in(rk, 1), ctx)
                state = strategy.sharded_aggregate(
                    state, r, jax.random.fold_in(rk, 2), ctx)
                return state, (metrics, {})
            # the realization is replicated (the fault carry is stepped from
            # the phase key on every slice), so active matches single-device
            active = af.real.active()
            local_active = ctx.shard_rows(active)
            new, metrics = strategy.sharded_local_update(
                state, xs, ys, r, jax.random.fold_in(rk, 1), ctx)
            new = strategy.merge_participation(state, new, local_active)
            new = strategy.sharded_aggregate_masked(
                new, r, jax.random.fold_in(rk, 2), ctx, active, local_active)
            new = strategy.merge_participation(state, new, local_active)
            empty = jnp.sum(active) == 0
            state = jax.tree_util.tree_map(
                lambda s, n: jnp.where(empty, s, n), state, new)
            return state, (metrics, {"participation": active})

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

    def round_body(self, strategy, batch_size):
        def body(state, r, phase_key, train_x, train_y):
            M = train_y.shape[0]
            rk = jax.random.fold_in(phase_key, r)
            xs, ys = sample_client_batches(
                train_x, train_y, jax.random.fold_in(rk, 0), batch_size)
            mask = self.draw_mask(jax.random.fold_in(rk, 3), M)
            af = current_faults()
            if af is not None:
                # a sampled client that is down or slow still can't serve
                mask = mask * af.real.active()
            new, metrics = strategy.local_update(
                state, xs, ys, r, jax.random.fold_in(rk, 1))
            # absent clients' local training is discarded: aggregation sees
            # their pre-round (last-known) state
            new = strategy.merge_participation(state, new, mask)
            new = strategy.aggregate_masked(new, r, jax.random.fold_in(rk, 2),
                                            mask)
            # ...and they receive nothing: final state is bit-unchanged
            new = strategy.merge_participation(state, new, mask)
            # empty Bernoulli cohort ⇒ the round is a no-op for everyone
            # (stacked strategies are already frozen by the merges; this
            # guards server-style states whose cohort-weighted aggregation
            # has no cohort to weight)
            empty = jnp.sum(mask) == 0
            state = jax.tree_util.tree_map(
                lambda s, n: jnp.where(empty, s, n), state, new)
            return state, (metrics, {"participation": mask})

        return body

    def paged_round_body(self, strategy, batch_size, pctx):
        """Round body over a paged cohort (``repro.engine.population``): the
        chunk's arrays hold the compact (C, ...) cohort rows, but every random
        draw is made at full population size — the (M,) participation mask,
        the M-way per-client key split, the (M, B) batch-index draw — and then
        sliced at the cohort's global ids, so the streams (and the aux
        participation masks the ledger and byte accounting consume) are
        bit-identical to the resident body's."""
        def body(state, r, phase_key, train_x, train_y):
            rk = jax.random.fold_in(phase_key, r)
            xs, ys = pctx.sample_cohort_batches(
                train_x, train_y, jax.random.fold_in(rk, 0), batch_size)
            mask = self.draw_mask(jax.random.fold_in(rk, 3), pctx.M)
            af = current_faults()
            if af is not None:
                mask = mask * af.real.active()
            # cohort-slot view of the full mask; padding slots never merge
            mask_c = mask[pctx.ids_clip] * pctx.valid
            new, metrics = strategy.paged_local_update(
                state, xs, ys, r, jax.random.fold_in(rk, 1), pctx)
            new = strategy.merge_participation(state, new, mask_c)
            new = strategy.paged_aggregate_masked(
                new, r, jax.random.fold_in(rk, 2), mask, pctx)
            new = strategy.merge_participation(state, new, mask_c)
            empty = jnp.sum(mask) == 0
            state = jax.tree_util.tree_map(
                lambda s, n: jnp.where(empty, s, n), state, new)
            return state, (metrics, {"participation": mask})

        return body

    def sharded_round_body(self, strategy, batch_size, ctx):
        def body(state, r, phase_key, train_x, train_y):
            rk = jax.random.fold_in(phase_key, r)
            xs, ys = ctx.sample_local_batches(
                train_x, train_y, jax.random.fold_in(rk, 0), batch_size)
            # the full (M,) mask is drawn replicated — every shard computes
            # the same draw the single-device body makes, then slices its own
            # rows; the aux output stays the full mask so byte accounting and
            # the ledger see exactly the single-device cohorts
            mask = self.draw_mask(jax.random.fold_in(rk, 3), ctx.M)
            af = current_faults()
            if af is not None:
                mask = mask * af.real.active()
            local_mask = ctx.shard_rows(mask)
            new, metrics = strategy.sharded_local_update(
                state, xs, ys, r, jax.random.fold_in(rk, 1), ctx)
            new = strategy.merge_participation(state, new, local_mask)
            new = strategy.sharded_aggregate_masked(
                new, r, jax.random.fold_in(rk, 2), ctx, mask, local_mask)
            new = strategy.merge_participation(state, new, local_mask)
            empty = jnp.sum(mask) == 0
            state = jax.tree_util.tree_map(
                lambda s, n: jnp.where(empty, s, n), state, new)
            return state, (metrics, {"participation": mask})

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

    def round_body(self, strategy, batch_size):
        period = int(self.staleness) + 1
        weight = float(period ** (-self.staleness_pow))

        def body(state, r, phase_key, train_x, train_y):
            rk = jax.random.fold_in(phase_key, r)
            xs, ys = sample_client_batches(
                train_x, train_y, jax.random.fold_in(rk, 0), batch_size)
            af = current_faults()
            if af is not None:
                # realized staleness: each client's merge weight comes from
                # the rounds it actually missed ((1+age)^-pow, FedBuff form)
                # instead of the configured scalar s — slow devices emerge
                # from the straggler chain
                active = af.real.active()
                new, metrics = strategy.local_update(
                    state, xs, ys, r, jax.random.fold_in(rk, 1))
                new = strategy.merge_participation(state, new, active)
                agg = strategy.aggregate_masked(
                    new, r, jax.random.fold_in(rk, 2), active)
                w = (1.0 + af.real.age) ** (-self.staleness_pow)
                if strategy.state_client_stacked(state):
                    merged = _fault_blend(agg, new, w, w.shape[0],
                                          jnp.mean(w))
                    hold = new
                else:
                    # server-style state: the aggregate folds into the
                    # previous global model at the mean realized discount
                    wbar = jnp.mean(w)
                    merged = jax.tree_util.tree_map(
                        lambda a, s: (wbar * a + (1.0 - wbar) * s)
                        .astype(s.dtype), agg, state)
                    hold = state
                if period > 1:
                    is_merge = jnp.equal(r % period, period - 1)
                    merged = jax.tree_util.tree_map(
                        lambda m, n: jnp.where(is_merge, m, n), merged, hold)
                merged = strategy.merge_participation(state, merged, active)
                empty = jnp.sum(active) == 0
                state = jax.tree_util.tree_map(
                    lambda s, n: jnp.where(empty, s, n), state, merged)
                return state, (metrics, {"participation": active})
            state, metrics = strategy.local_update(
                state, xs, ys, r, jax.random.fold_in(rk, 1))
            if period == 1:   # synchronous: identical to FullParticipation
                state = strategy.aggregate(state, r, jax.random.fold_in(rk, 2))
                return state, (metrics, {})

            def merge(s):
                agg = strategy.aggregate(s, r, jax.random.fold_in(rk, 2))
                return jax.tree_util.tree_map(
                    lambda a, b: (weight * a + (1.0 - weight) * b).astype(b.dtype),
                    agg, s)

            state = jax.lax.cond(jnp.equal(r % period, period - 1),
                                 merge, lambda s: s, state)
            return state, (metrics, {})

        return body

    def sharded_round_body(self, strategy, batch_size, ctx):
        period = int(self.staleness) + 1
        weight = float(period ** (-self.staleness_pow))

        def body(state, r, phase_key, train_x, train_y):
            rk = jax.random.fold_in(phase_key, r)
            xs, ys = ctx.sample_local_batches(
                train_x, train_y, jax.random.fold_in(rk, 0), batch_size)
            af = current_faults()
            if af is not None:
                active = af.real.active()
                local_active = ctx.shard_rows(active)
                new, metrics = strategy.sharded_local_update(
                    state, xs, ys, r, jax.random.fold_in(rk, 1), ctx)
                new = strategy.merge_participation(state, new, local_active)
                agg = strategy.sharded_aggregate_masked(
                    new, r, jax.random.fold_in(rk, 2), ctx, active,
                    local_active)
                w = (1.0 + af.real.age) ** (-self.staleness_pow)
                if strategy.state_client_stacked(state):
                    merged = _fault_blend(agg, new, ctx.shard_rows(w), ctx.m,
                                          jnp.mean(w))
                    hold = new
                else:
                    wbar = jnp.mean(w)
                    merged = jax.tree_util.tree_map(
                        lambda a, s: (wbar * a + (1.0 - wbar) * s)
                        .astype(s.dtype), agg, state)
                    hold = state
                if period > 1:
                    is_merge = jnp.equal(r % period, period - 1)
                    merged = jax.tree_util.tree_map(
                        lambda m, n: jnp.where(is_merge, m, n), merged, hold)
                merged = strategy.merge_participation(state, merged,
                                                      local_active)
                empty = jnp.sum(active) == 0
                state = jax.tree_util.tree_map(
                    lambda s, n: jnp.where(empty, s, n), state, merged)
                return state, (metrics, {"participation": active})
            state, metrics = strategy.sharded_local_update(
                state, xs, ys, r, jax.random.fold_in(rk, 1), ctx)
            if period == 1:   # synchronous: identical to FullParticipation
                state = strategy.sharded_aggregate(
                    state, r, jax.random.fold_in(rk, 2), ctx)
                return state, (metrics, {})
            # collectives must execute uniformly across shards, so the merge
            # is select-based rather than lax.cond: the aggregate (and its
            # all_gather/psum) runs every round and non-merge rounds select
            # the untouched state — bit-identical outcomes, uniform comms
            agg = strategy.sharded_aggregate(
                state, r, jax.random.fold_in(rk, 2), ctx)
            merged = jax.tree_util.tree_map(
                lambda a, b: (weight * a + (1.0 - weight) * b).astype(b.dtype),
                agg, state)
            is_merge = jnp.equal(r % period, period - 1)
            state = jax.tree_util.tree_map(
                lambda m, s: jnp.where(is_merge, m, s), merged, state)
            return state, (metrics, {})

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
