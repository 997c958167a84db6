"""Property tier for the host-resident client population + paged cohorts
(``repro.engine.population``) — ISSUE 8.

The fast half of the paged ≡ resident lock: the multi-strategy equivalence
scenarios live in the 8-device subprocess (``test_sharded_engine.py``); here
the contract's individual properties are pinned in-process —

  * gather → scatter round-trips leave untouched clients bit-identical;
  * per-client PRNG streams are keyed by GLOBAL client id, invariant to the
    client's cohort slot (and hence to cohort padding width);
  * the PrivacyLedger advances identically under paged and resident
    execution at equal q·M, so the reported (ε, δ) is computed against the
    full population;
  * the double-buffered prefetch never serves a stale cohort: a scatter
    between a prefetched gather and its take forces a re-gather (version
    check), and a prefetching run stays bit-exact with a non-prefetching
    one;

plus the tier-1 M=4096 paged smoke gate: a population 64× larger than the
materialized cohort trains, pages, and matches the resident engine.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.baselines.dp_dsgt import DPDSGTStrategy
from repro.baselines.local import LocalStrategy
from repro.config import DPConfig
from repro.engine import (ClientSampling, Engine, FederatedData,
                          HostFederatedData, PagedCtx, PagedEngine,
                          PrivacyLedger, VirtualPopulation)


def _toy(rng, M=8, feat=12, classes=3, n=32):
    protos = rng.normal(size=(classes, feat)).astype(np.float32) * 3
    ys = rng.integers(0, classes, size=(M, n))
    xs = protos[ys] + rng.normal(size=(M, n, feat)).astype(np.float32) * 0.4
    return FederatedData(xs, ys.astype(np.int32), jnp.asarray(xs),
                         jnp.asarray(ys.astype(np.int32)))


def _leaves(tree):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(tree)]


def _assert_trees_equal(a, b):
    for x, y in zip(_leaves(a), _leaves(b)):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# VirtualPopulation: gather/scatter round-trip
# ---------------------------------------------------------------------------

def test_gather_scatter_roundtrip_identity(rng):
    """Scattering a cohort back leaves every untouched client bit-identical,
    writes exactly the cohort rows, and tracks them as dirty."""
    M = 32
    pop = VirtualPopulation(M)
    a0 = rng.normal(size=(M, 5)).astype(np.float32)
    a1 = rng.normal(size=(M, 2, 3)).astype(np.float64)
    pop.add(a0.copy())
    pop.add(a1.copy())
    rows = np.array([3, 7, 8, 21, 30])
    got = pop.gather(rows)
    np.testing.assert_array_equal(got[0], a0[rows])
    np.testing.assert_array_equal(got[1], a1[rows])

    v0 = pop.version
    new = [g + 1.0 for g in got]
    pop.scatter(rows, new)
    assert pop.version == v0 + 1
    np.testing.assert_array_equal(pop.dirty_rows(), rows)
    untouched = np.setdiff1d(np.arange(M), rows)
    np.testing.assert_array_equal(pop.arrays[0][untouched], a0[untouched])
    np.testing.assert_array_equal(pop.arrays[1][untouched], a1[untouched])
    np.testing.assert_array_equal(pop.arrays[0][rows], a0[rows] + 1.0)
    np.testing.assert_array_equal(pop.arrays[1][rows], a1[rows] + 1.0)

    # gather returns copies: mutating them must not reach the store
    got2 = pop.gather(rows)
    got2[0][:] = -1.0
    np.testing.assert_array_equal(pop.arrays[0][rows], a0[rows] + 1.0)


# ---------------------------------------------------------------------------
# PRNG streams: keyed by global client id, invariant to cohort slot
# ---------------------------------------------------------------------------

def test_prng_streams_invariant_to_slot_permutation(key, rng):
    """Permuting the cohort's slot layout permutes — but never changes — each
    client's key and batch draw: both are sliced from the full-M draw at the
    cohort's GLOBAL ids."""
    M, C, R, B = 16, 8, 10, 4
    ids = np.array([3, 7, 1, 11, 15, 0, M, M], np.int32)   # 2 padding slots
    perm = rng.permutation(C)
    ids_p = ids[perm]
    tx = rng.normal(size=(M, R, 5)).astype(np.float32)
    ty = rng.integers(0, 3, size=(M, R)).astype(np.int32)

    def draws(cohort_ids):
        ctx = PagedCtx(M, C)
        clip = np.minimum(cohort_ids, M - 1)
        valid = (cohort_ids < M).astype(np.float32)
        with ctx.installed(jnp.asarray(cohort_ids), jnp.asarray(valid)):
            ks = np.asarray(ctx.client_keys(key))
            xs, ys = ctx.sample_batches(
                jnp.asarray(tx[clip]), jnp.asarray(ty[clip]), key, B)
        return ks, np.asarray(xs), np.asarray(ys)

    k1, x1, y1 = draws(ids)
    k2, x2, y2 = draws(ids_p)
    full_keys = np.asarray(jax.random.split(key, M))
    for s2 in range(C):
        s1 = int(perm[s2])   # original slot holding the same global id
        np.testing.assert_array_equal(k2[s2], k1[s1])
        np.testing.assert_array_equal(x2[s2], x1[s1])
        np.testing.assert_array_equal(y2[s2], y1[s1])
        if ids_p[s2] < M:   # and the stream really is the global split's row
            np.testing.assert_array_equal(k2[s2], full_keys[ids_p[s2]])


def test_final_state_invariant_to_cohort_padding(key, rng):
    """Different ``cohort_pad`` buckets change the compiled chunk's padded
    width and every client's slot — the result must not move by a bit."""
    data = _toy(rng)
    finals = []
    for pad in (3, 8, 16):
        st, h = PagedEngine(
            DPDSGTStrategy(feat_dim=12, num_classes=3, lr=0.3, clip=1.0,
                           sigma=0.4),
            eval_every=3, schedule=ClientSampling(q=0.5),
            cohort_pad=pad).fit(data, rounds=6, key=key, batch_size=8)
        finals.append((st, h))
    for st, h in finals[1:]:
        _assert_trees_equal(st, finals[0][0])
        assert h.accuracy == finals[0][1].accuracy
        assert h.metrics == finals[0][1].metrics


# ---------------------------------------------------------------------------
# PrivacyLedger: identical (ε, δ) at equal q·M
# ---------------------------------------------------------------------------

def test_ledger_identical_between_paged_and_resident(key, rng):
    """The ledger advances per EXECUTED ROUND against the full population's
    sampling rates — paging must not change the accounted (ε, δ) even though
    the device only ever sees q·M clients."""
    data = _toy(rng)

    def run(engine_cls):
        ledger = PrivacyLedger(sigma=0.8, delta=1e-3, sample_rate=0.25,
                               client_rate=0.5)
        eng = engine_cls(
            LocalStrategy(feat_dim=12, num_classes=3, lr=0.5,
                          dp_cfg=DPConfig(clip_norm=1.0), sigma=0.8),
            eval_every=2, schedule=ClientSampling(q=0.5), ledger=ledger)
        _, h = eng.fit(data, rounds=6, key=key, batch_size=8)
        return ledger, h

    led1, h1 = run(Engine)
    led2, h2 = run(PagedEngine)
    assert led1.rounds_seen == led2.rounds_seen == 6
    assert h1.metrics["dp_epsilon"] == h2.metrics["dp_epsilon"]
    assert h1.metrics["dp_delta"] == h2.metrics["dp_delta"]
    assert led1.epsilon() == led2.epsilon()


# ---------------------------------------------------------------------------
# Prefetch double-buffering: never a stale cohort
# ---------------------------------------------------------------------------

def test_prefetch_never_serves_stale_state(key, rng):
    """A scatter between a prefetched gather and its take bumps the
    population version; the take must re-gather rather than serve the stale
    rows."""
    data = _toy(rng)
    eng = PagedEngine(LocalStrategy(feat_dim=12, num_classes=3, lr=0.5),
                      eval_every=100, schedule=ClientSampling(q=0.5))
    eng.fit(data, rounds=2, key=key, batch_size=8, evaluate=False)

    gids = np.array([0, 2, 5, 6], np.int64)
    payload = eng._gather_payload(gids)
    payload["C"] = len(gids)
    eng._prefetcher.submit((5, 9, None), lambda: payload)
    # a chunk scatters while the prefetched payload waits
    bump = [a[np.array([2])] + 1.0 for a in eng._pop.arrays]
    eng._pop.scatter(np.array([2]), bump)
    stale_before = eng._prefetcher.stats["stale"]
    out = eng._take_cohort((5, 9, len(gids)), gids)
    assert eng._prefetcher.stats["stale"] == stale_before + 1
    assert out["version"] == eng._pop.version
    for i, a in enumerate(eng._pop.arrays):
        np.testing.assert_array_equal(out["state"][i], a[gids])


def test_prefetching_run_is_bit_exact_and_validated(key, rng):
    """End-to-end: a prefetching paged run matches a non-prefetching one
    bitwise even though every hit payload's state rows were gathered before
    the previous chunk's scatter landed (the take-time version check
    re-gathers them)."""
    data = _toy(rng)

    def run(prefetch):
        eng = PagedEngine(
            DPDSGTStrategy(feat_dim=12, num_classes=3, lr=0.3, clip=1.0,
                           sigma=0.4),
            eval_every=2, schedule=ClientSampling(q=0.5), prefetch=prefetch)
        st, h = eng.fit(data, rounds=6, key=key, batch_size=8)
        return st, h, eng._prefetcher.stats

    st1, h1, _ = run(False)
    st2, h2, stats = run(True)
    _assert_trees_equal(st1, st2)
    assert h1.accuracy == h2.accuracy and h1.metrics == h2.metrics
    assert stats["submitted"] > 0
    assert stats["hits"] >= 1, stats
    # whether a hit also counted as stale depends on gather/scatter timing —
    # but a stale count can never exceed the hits that were checked
    assert stats["stale"] <= stats["hits"], stats


# ---------------------------------------------------------------------------
# tier-1 gate: M=4096 paged smoke
# ---------------------------------------------------------------------------

def test_paged_smoke_m4096(key, rng):
    """A 4096-client population trains with only a ~64-wide cohort
    materialized per round and matches the resident engine bit-exactly —
    the minimal in-tier million-client gate (the full curve lives in
    ``benchmarks/bench_population.py``)."""
    M, feat, classes, n = 4096, 8, 2, 4
    protos = rng.normal(size=(classes, feat)).astype(np.float32) * 3
    ys = rng.integers(0, classes, size=(M, n))
    xs = protos[ys] + rng.normal(size=(M, n, feat)).astype(np.float32) * 0.5
    host = HostFederatedData(xs, ys.astype(np.int32), xs,
                             ys.astype(np.int32))
    data = FederatedData(xs, ys.astype(np.int32), jnp.asarray(xs),
                         jnp.asarray(ys.astype(np.int32)))
    q = 64 / M

    def mk():
        return LocalStrategy(feat_dim=feat, num_classes=classes, lr=0.5)

    st2, h2 = PagedEngine(mk(), eval_every=2,
                          schedule=ClientSampling(q=q)).fit(
        host, rounds=4, key=key, batch_size=None)
    st1, h1 = Engine(mk(), eval_every=2, schedule=ClientSampling(q=q)).fit(
        data, rounds=4, key=key, batch_size=None)
    assert h1.rounds == h2.rounds and h1.accuracy == h2.accuracy
    assert h1.metrics == h2.metrics
    _assert_trees_equal(st1, st2)
