"""Multi-device equivalence tier for the sharded federation engine.

The 8-device scenarios run once in a subprocess (XLA's fake-device flag must
precede jax init — same recipe as the mini dry-run) executing
``tests/_sharded_equivalence_main.py``; each test asserts one scenario's
record, so failures name the exact (strategy × schedule × layout) combo.

In-process tests cover the pieces that don't need fake devices: the global
compiled-chunk cache (σ-sweep reuse + cache_token invalidation), the
calibrate-then-resume ledger composition, host-mesh clamping, and the
degenerate 1-slice client mesh (which exercises the whole shard_map path on
the single real device, keeping the plumbing honest inside tier-1's fast
set)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.baselines.local import LocalStrategy
from repro.config import DPConfig
from repro.engine import (CHUNK_STATS, ClientSampling, Engine, FederatedData,
                          PrivacyLedger, ShardedEngine, Strategy,
                          clear_chunk_cache)
from repro.launch.mesh import host_mesh_shape, make_client_mesh


# ---------------------------------------------------------------------------
# 8-fake-device equivalence scenarios (subprocess, module-scoped: one jax
# startup + compile budget amortized over every assertion below)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def equivalence():
    script = os.path.join(os.path.dirname(__file__),
                          "_sharded_equivalence_main.py")
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"),
    )
    p = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


# Final-state bounds for the scenarios whose states are not bit-equal across
# layouts. Rounds, accuracy histories, metrics, groups and byte accounting
# stay bit-exact everywhere; only final parameters differ, by float ulps,
# because XLA compiles the per-client arithmetic once per layout (8 clients
# in one program vs 1 per device): LLVM contracts multiply-adds into FMAs
# differently per program (with --xla_cpu_max_isa=SSE4_2 the non-aggregating
# scenarios turn bit-exact), and the client-axis sums of a gathered stack
# (FedAvg, ProxyFL, P4's gathered group mean) are reduced in a per-program
# order (≤ 4.2e-7 even without FMAs) — the effect the DP-DSGT bound below
# already covers. Measured maxima on the 8-device CPU mesh: 3.6e-7 outside
# P4's gathered group mean, 2.0e-6 on it.
STATE_ULPS = 5e-7
P4_GATHER_STATE_ULPS = 3e-6


def _assert_bit_exact(rec, state_tol=None):
    """Rounds and accuracy bit-exact; the final state bit-exact, or within
    ``state_tol`` for a scenario listed above."""
    assert rec["rounds_equal"]
    assert rec["accuracy_bit_equal"], rec
    if state_tol is None:
        assert rec["state_bit_equal"], rec
    else:
        assert rec["state_maxdiff"] < state_tol, rec


@pytest.mark.slow
def test_subprocess_saw_eight_devices(equivalence):
    assert equivalence["devices"] == 8


@pytest.mark.slow
def test_full_participation_bit_exact_histories(equivalence):
    """ISSUE 4 acceptance: sharded FullParticipation histories (and states,
    where the backend's fusion allows) are bit-exact vs the single-device
    engine for p4 / fedavg (gather reduction) / dp_dsgt."""
    _assert_bit_exact(equivalence["local_full"])
    _assert_bit_exact(equivalence["p4_full_resident"])
    _assert_bit_exact(equivalence["fedavg_full"], STATE_ULPS)
    _assert_bit_exact(equivalence["p4_full_gather"], P4_GATHER_STATE_ULPS)
    # DP-DSGT's gossip runs as a ppermute halo exchange; XLA contracts the
    # mix's multiply-adds differently per layout, so states agree to float
    # ulps while the recorded histories stay bit-equal
    rec = equivalence["dsgt_full"]
    assert rec["rounds_equal"] and rec["accuracy_bit_equal"], rec
    assert rec["state_maxdiff"] < 1e-6, rec


@pytest.mark.slow
def test_uneven_padding_bit_exact(equivalence):
    """M % devices != 0: padded slots never leak into results."""
    _assert_bit_exact(equivalence["local_full_uneven"])
    _assert_bit_exact(equivalence["local_sampling_uneven"], STATE_ULPS)
    rec = equivalence["dsgt_full_uneven"]
    assert rec["rounds_equal"] and rec["accuracy_bit_equal"], rec
    assert rec["state_maxdiff"] < 1e-6, rec


@pytest.mark.slow
def test_client_sampling_equivalence(equivalence):
    """Sampling draws the identical (M,) cohort mask on every slice; states
    match to tight tolerance (bit-exact for the gather-aggregated ones)."""
    _assert_bit_exact(equivalence["fedavg_sampling"], STATE_ULPS)
    _assert_bit_exact(equivalence["p4_sampling"], STATE_ULPS)
    _assert_bit_exact(equivalence["p4_sampling_resident"])
    rec = equivalence["dsgt_sampling"]
    assert rec["rounds_equal"] and rec["accuracy_maxdiff"] < 1e-5, rec
    assert rec["state_maxdiff"] < 1e-6, rec


@pytest.mark.slow
def test_async_staleness_equivalence(equivalence):
    # s=0 ≡ synchronous
    _assert_bit_exact(equivalence["fedavg_async0"], STATE_ULPS)
    for name, tol in (("p4_async1", P4_GATHER_STATE_ULPS),
                      ("dsgt_async2", 1e-6)):
        rec = equivalence[name]
        assert rec["rounds_equal"] and rec["accuracy_maxdiff"] < 1e-5, rec
        assert rec["state_maxdiff"] < tol, (name, rec)


@pytest.mark.slow
def test_fedavg_psum_tree_reduction(equivalence):
    """ISSUE 5 satellite: the default psum-tree cohort mean is bit-close to
    both the single-device engine and the gather path on the same mesh."""
    for name in ("fedavg_psum_full", "fedavg_psum_sampling"):
        rec = equivalence[name]
        assert rec["rounds_equal"] and rec["accuracy_maxdiff"] < 1e-5, rec
        assert rec["state_maxdiff"] < 1e-5, (name, rec)
    rec = equivalence["fedavg_psum_vs_gather"]
    assert rec["rounds_equal"] and rec["state_maxdiff"] < 1e-5, rec


@pytest.mark.slow
def test_scaffold_proxyfl_sharded_ports(equivalence):
    """ISSUE 5 satellite (open ROADMAP item): Scaffold and ProxyFL run under
    the ShardedEngine — bit-exact vs single-device, including the mixed
    stacked/replicated Scaffold carry and uneven padding."""
    for name in ("scaffold_full", "scaffold_sampling", "scaffold_uneven"):
        _assert_bit_exact(equivalence[name])
    for name in ("proxyfl_full", "proxyfl_uneven"):
        _assert_bit_exact(equivalence[name], STATE_ULPS)


@pytest.mark.slow
def test_dsgt_topology_equivalence(equivalence):
    """ISSUE 5 acceptance: sharded ≡ single-device for a non-ring topology
    (4-regular expander, gossip-matching sequence) and the shard-resident
    slice-local mixing path."""
    for name in ("dsgt_topology_expander", "dsgt_gossip_sequence",
                 "dsgt_topology_resident"):
        rec = equivalence[name]
        assert rec["rounds_equal"] and rec["accuracy_bit_equal"], (name, rec)
        assert rec["state_maxdiff"] < 1e-6, (name, rec)


@pytest.mark.slow
def test_dsgt_faulty_topology_equivalence(equivalence):
    """ISSUE 5 acceptance: a faulty run (drop probability > 0) — the in-jit
    fault draws are replicated, so every slice realizes the same topology."""
    for name in ("dsgt_topology_faulty", "dsgt_topology_resident_faulty"):
        rec = equivalence[name]
        assert rec["rounds_equal"] and rec["accuracy_maxdiff"] < 1e-5, (name,
                                                                        rec)
        assert rec["state_maxdiff"] < 1e-6, (name, rec)


@pytest.mark.slow
def test_dsgt_learned_pushsum_equivalence(equivalence):
    """ISSUE 9 acceptance: learned directed graphs (column-stochastic W,
    push-sum weight scalar riding the x mix as a joint leaf) stay sharded ≡
    single-device — static estimate, a two-estimate time-varying window, and
    a faulted run (the sender-side diagonal fold keeps the realized matrix
    column-stochastic)."""
    for name in ("dsgt_learned_pushsum", "dsgt_learned_timevarying"):
        rec = equivalence[name]
        assert rec["rounds_equal"] and rec["accuracy_bit_equal"], (name, rec)
        assert rec["state_maxdiff"] < 1e-6, (name, rec)
    rec = equivalence["dsgt_learned_faulty"]
    assert rec["rounds_equal"] and rec["accuracy_maxdiff"] < 1e-5, rec
    assert rec["state_maxdiff"] < 1e-6, rec


@pytest.mark.slow
def test_banded_topologies_gather_free(equivalence):
    """ISSUE 7 acceptance: banded/bounded-bandwidth graphs (ring, faulty
    ring, keep-masked ring, torus, circulant expander) never fall back to
    the all_gather mixing path — the collective probe records only halo
    ppermutes for them (per chunk trace, so 0 gathers is 0 outright)."""
    for name in ("dsgt_full", "dsgt_ring_faulty", "dsgt_ring_burst",
                 "dsgt_torus", "dsgt_topology_expander",
                 "dsgt_topology_faulty"):
        stats = equivalence[name]["mix_stats"]
        assert stats["all_gathers"] == 0, (name, stats)
        assert stats["path_gather"] == 0, (name, stats)
        assert stats["ppermutes"] > 0, (name, stats)
        assert stats["path_halo"] > 0, (name, stats)
    # shard-resident layout: no collective of either kind in the mix
    stats = equivalence["dsgt_topology_resident"]["mix_stats"]
    assert stats["all_gathers"] == 0 and stats["ppermutes"] == 0, stats
    assert stats["path_local"] > 0, stats


@pytest.mark.slow
def test_banded_faulty_equivalence(equivalence):
    """ISSUE 7 satellite: keep-masked / i.i.d.-faulty rings and the torus
    route through the halo path AND stay equivalent to single-device."""
    for name in ("dsgt_ring_faulty", "dsgt_ring_burst", "dsgt_torus"):
        rec = equivalence[name]
        assert rec["rounds_equal"] and rec["accuracy_maxdiff"] < 1e-5, (name,
                                                                       rec)
        assert rec["state_maxdiff"] < 1e-6, (name, rec)


@pytest.mark.slow
def test_topology_resident_layout(equivalence):
    layout = equivalence["topology_resident_layout"]
    assert layout["resident_on_2"] is True
    assert layout["resident_on_8"] is False   # m=1: 4-cliques must span


@pytest.mark.slow
def test_p4_fault_injection_equivalence(equivalence):
    """Fault-injected P4 group rounds: the member↔aggregator drop masks
    realize identically on the resident (sliced mask) and gather layouts."""
    for name, tol in (("p4_faulty_resident", 1e-6),
                      ("p4_faulty_gather", P4_GATHER_STATE_ULPS)):
        rec = equivalence[name]
        assert rec["rounds_equal"] and rec["accuracy_maxdiff"] < 1e-5, (name,
                                                                        rec)
        assert rec["state_maxdiff"] < tol, (name, rec)


@pytest.mark.slow
def test_correlated_fault_regimes_equivalence(equivalence):
    """ISSUE 6 acceptance: sharded ≡ single-device under the stateful fault
    chains — the ``FaultState`` carry is replicated, so every slice steps the
    identical Gilbert–Elliott / churn / partition realization."""
    for name in ("dsgt_fault_burst", "dsgt_fault_churn",
                 "dsgt_fault_partition"):
        rec = equivalence[name]
        assert rec["rounds_equal"] and rec["accuracy_bit_equal"], (name, rec)
        assert rec["state_maxdiff"] < 1e-6, (name, rec)


@pytest.mark.slow
def test_straggler_chain_equivalence(equivalence):
    """Straggler chains feed AsyncStaleness the realized per-client ages;
    the fault-blended merge matches bit-exactly (FedAvg's server-style fold)
    or to float ulps (P4's stacked per-client blend)."""
    _assert_bit_exact(equivalence["fedavg_fault_straggler"], STATE_ULPS)
    rec = equivalence["p4_fault_straggler"]
    assert rec["rounds_equal"] and rec["accuracy_bit_equal"], rec
    assert rec["state_maxdiff"] < 1e-6, rec


@pytest.mark.slow
def test_aggregator_failover_equivalence(equivalence):
    """Node churn + quorum: the traced failover mask (next-up aggregator,
    below-quorum groups silenced) realizes identically on the resident and
    gather layouts."""
    _assert_bit_exact(equivalence["p4_fault_failover_resident"])
    _assert_bit_exact(equivalence["p4_fault_failover_gather"],
                      P4_GATHER_STATE_ULPS)


@pytest.mark.slow
def test_p4_group_layouts(equivalence):
    """Groups that fit one slice aggregate without any collective; spanning
    groups take the gather path — both bit-exact."""
    layout = equivalence["p4_resident_layout"]
    assert layout["resident_on_2"] is True
    assert layout["resident_on_8"] is False   # m=1: a group of 4 must span


@pytest.mark.slow
def test_paged_engine_bit_exact(equivalence):
    """ISSUE 8 acceptance (paged ≡ resident tier): the host-resident
    population with paged cohorts reproduces the resident engine bit-exactly
    — final state AND full History (accuracy + every metric) — for
    p4 / fedavg / dp_dsgt across full / sampling / async schedules,
    including uneven cohort sizes (M=6 fixed-k, Bernoulli draws) and a
    non-ring gossip graph whose in-neighbor closure the cohort planner must
    page in."""
    for name in ("paged_fedavg_full", "paged_fedavg_sampling_uneven",
                 "paged_fedavg_bernoulli", "paged_fedavg_async0",
                 "paged_dsgt_full", "paged_dsgt_sampling",
                 "paged_dsgt_sampling_uneven", "paged_dsgt_async2",
                 "paged_dsgt_expander_sampling", "paged_p4_full",
                 "paged_p4_async1"):
        rec = equivalence[name]
        _assert_bit_exact(rec, STATE_ULPS
                          if name == "paged_fedavg_bernoulli" else None)
        assert rec["metrics_bit_equal"], (name, rec)


@pytest.mark.slow
def test_paged_engine_p4_sampling(equivalence):
    """P4 under sampling: state, accuracy, and every non-train metric stay
    bit-exact; the train-loss means are the one documented paged difference
    (cohort mean vs the resident's full-M mean over never-aggregated local
    passes) and only need to stay in-range."""
    rec = equivalence["paged_p4_sampling"]
    _assert_bit_exact(rec)
    assert rec["metrics_bit_equal"], rec
    assert rec["excluded_maxdiff"] < 2.0, rec


@pytest.mark.slow
def test_paged_engine_fault_regime(equivalence):
    """Paged ≡ resident under a correlated node-churn process: the planned
    cohort is a superset of realized participants (faults only remove
    clients), the fault carry is full-M, and absent clients stay
    bit-frozen."""
    rec = equivalence["paged_fedavg_sampling_faulty"]
    _assert_bit_exact(rec, STATE_ULPS)
    assert rec["metrics_bit_equal"], rec


@pytest.mark.slow
def test_paged_engine_cohort_mesh(equivalence):
    """Cohort axis sharded over the 8-device clients mesh (GSPMD partition
    of the paged chunk): numerically tight vs the resident engine (bit-level
    agreement is not contractual — partitioned reductions may
    reassociate)."""
    rec = equivalence["paged_mesh_fedavg_sampling"]
    assert rec["rounds_equal"], rec
    assert rec["accuracy_maxdiff"] < 1e-5, rec
    assert rec["state_maxdiff"] < 1e-5, rec


@pytest.mark.slow
def test_telemetry_off_and_tap_on_equivalence(equivalence):
    """ISSUE 10 zero-overhead-off contract on the sharded path: a disabled
    Telemetry builds the unchanged chunk-cache key and is bit-exact with no
    telemetry at all — and an ENABLED tap is bit-exact too, because the
    sharded trace stays tap-free (per-round events stream host-side from
    the stacked chunk outputs, covering every round exactly once)."""
    rec = equivalence["telemetry_off_sharded"]
    assert rec["chunk_key_unchanged"], rec
    assert rec["rounds_equal"] and rec["accuracy_bit_equal"], rec
    assert rec["state_bit_equal"], rec
    assert rec["tap_rounds"] == list(range(8)), rec


@pytest.mark.slow
def test_p4_end_to_end_bit_exact(equivalence):
    """Whole trainer pipeline under a client mesh: bootstrap, host-side
    greedy grouping (identical groups — the bootstrap states are bit-exact),
    co-training, privacy ledger."""
    rec = equivalence["p4_end_to_end"]
    assert rec["groups_equal"], rec
    assert rec["rounds_equal"] and rec["accuracy_bit_equal"], rec
    assert rec["state_maxdiff"] < P4_GATHER_STATE_ULPS, rec
    assert rec["metrics_maxdiff"] < 1e-6, rec


@pytest.mark.slow
def test_zero_byte_accounting_for_absent_clients(equivalence):
    """Sharded byte accounting sees the exact single-device cohorts: same
    message/byte totals, and every logged message has both endpoints in that
    round's cohort."""
    rec = equivalence["zero_byte_accounting"]
    assert rec["nonzero"] and rec["messages_equal"] and rec["bytes_equal"], rec
    assert rec["endpoints_in_cohort"], rec


# ---------------------------------------------------------------------------
# compiled-chunk cache: σ sweeps must not re-trace; cache_token bumps must
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def toy():
    rng = np.random.default_rng(0)
    M, feat, classes, n = 6, 12, 3, 32
    protos = rng.normal(size=(classes, feat)).astype(np.float32) * 3
    ys = rng.integers(0, classes, size=(M, n))
    xs = protos[ys] + rng.normal(size=(M, n, feat)).astype(np.float32) * 0.4
    X, Y = xs, ys.astype(np.int32)
    return FederatedData(X, Y, jnp.asarray(X), jnp.asarray(Y))


def _dp_local(sigma):
    return LocalStrategy(feat_dim=12, num_classes=3, lr=0.5,
                         dp_cfg=DPConfig(clip_norm=1.0), sigma=sigma)


def _leaves(tree):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(tree)]


def test_sigma_sweep_reuses_compiled_chunk(toy, key):
    """ISSUE 4 satellite: a sweep over σ with the same (length, batch_size,
    cache_token, mesh) compiles ONE chunk — σ reaches the trace as a runtime
    argument — and the reused chunk is bit-identical to a fresh compile."""
    clear_chunk_cache()
    finals = {}
    for sigma in (0.5, 0.9, 1.3):
        strat = _dp_local(sigma)
        st, _ = Engine(strat, eval_every=100).fit(
            toy, rounds=6, key=key, batch_size=8, evaluate=False)
        finals[sigma] = st
    assert CHUNK_STATS["traces"] == 1, CHUNK_STATS
    assert CHUNK_STATS["misses"] == 1 and CHUNK_STATS["hits"] == 2

    # σ actually flowed into the reused chunk: the noise differs
    assert not all(np.array_equal(a, b) for a, b in
                   zip(_leaves(finals[0.5]), _leaves(finals[1.3])))

    # reuse is bit-faithful: a cold-cache compile at σ=1.3 matches the state
    # the warm chunk produced
    clear_chunk_cache()
    fresh, _ = Engine(_dp_local(1.3), eval_every=100).fit(
        toy, rounds=6, key=key, batch_size=8, evaluate=False)
    for a, b in zip(_leaves(fresh), _leaves(finals[1.3])):
        np.testing.assert_array_equal(a, b)


def test_cache_token_bump_retraces(toy, key):
    clear_chunk_cache()
    strat = _dp_local(0.7)
    Engine(strat, eval_every=100).fit(toy, rounds=6, key=key, batch_size=8,
                                      evaluate=False)
    assert CHUNK_STATS["traces"] == 1
    strat.cache_token += 1   # what set_groups does between P4 phases
    Engine(strat, eval_every=100).fit(toy, rounds=6, key=key, batch_size=8,
                                      evaluate=False)
    assert CHUNK_STATS["traces"] == 2, CHUNK_STATS


def test_target_epsilon_recalibration_reuses_chunk(toy, key):
    """set_sigma no longer invalidates chunks: two target-ε runs share one
    compiled chunk and still land on their own budgets."""
    clear_chunk_cache()
    spent = {}
    for target in (6.0, 12.0):
        strat = _dp_local(1.0)
        ledger = PrivacyLedger(sigma=1.0, delta=1e-3, sample_rate=0.25)
        _, hist = Engine(strat, eval_every=100, ledger=ledger).fit(
            toy, rounds=8, key=key, batch_size=8, target_epsilon=target)
        spent[target] = hist.metrics["dp_epsilon"][-1]
    # the eval cadence splits 8 rounds into a length-1 and a length-7 chunk:
    # two traces for the FIRST target, pure cache hits for the second
    assert CHUNK_STATS["traces"] == 2, CHUNK_STATS
    for target, got in spent.items():
        assert abs(got - target) < 1e-6, spent


def test_sharded_mesh_is_part_of_the_cache_key(toy, key):
    """Same strategy fingerprint, different execution layout → different
    chunk; same layout twice → reuse."""
    clear_chunk_cache()
    mesh = make_client_mesh()   # 1 slice on the test host
    Engine(_dp_local(0.7), eval_every=100).fit(
        toy, rounds=6, key=key, batch_size=8, evaluate=False)
    ShardedEngine(_dp_local(0.7), eval_every=100, mesh=mesh).fit(
        toy, rounds=6, key=key, batch_size=8, evaluate=False)
    assert CHUNK_STATS["traces"] == 2, CHUNK_STATS
    ShardedEngine(_dp_local(0.9), eval_every=100, mesh=mesh).fit(
        toy, rounds=6, key=key, batch_size=8, evaluate=False)
    assert CHUNK_STATS["traces"] == 2, CHUNK_STATS


# ---------------------------------------------------------------------------
# degenerate 1-slice client mesh: the full shard_map path on the real device
# ---------------------------------------------------------------------------

def test_sharded_engine_single_slice_matches_engine(toy, key):
    st1, h1 = Engine(_dp_local(0.6), eval_every=3).fit(
        toy, rounds=7, key=key, batch_size=8)
    st2, h2 = ShardedEngine(_dp_local(0.6), eval_every=3,
                            mesh=make_client_mesh()).fit(
        toy, rounds=7, key=key, batch_size=8)
    assert h1.rounds == h2.rounds and h1.accuracy == h2.accuracy
    for a, b in zip(_leaves(st1), _leaves(st2)):
        np.testing.assert_array_equal(a, b)


def test_sharded_engine_rejects_unkeyed_strategy(toy, key):
    # scaffold/proxyfl are ported now — fabricate a strategy that only has
    # the unkeyed hook to keep the clean-rejection contract covered
    class UnkeyedStrategy(LocalStrategy):
        local_update_keyed = Strategy.local_update_keyed

    strat = UnkeyedStrategy(feat_dim=12, num_classes=3, lr=0.5)
    eng = ShardedEngine(strat, eval_every=100, mesh=make_client_mesh())
    with pytest.raises(NotImplementedError, match="local_update_keyed"):
        eng.fit(toy, rounds=2, key=key, batch_size=8, evaluate=False)


def test_sharded_engine_requires_client_axis(toy):
    import jax as _jax
    mesh = _jax.make_mesh((1, 1), ("data", "model"),
                          devices=_jax.devices()[:1])
    with pytest.raises(ValueError, match="clients"):
        ShardedEngine(_dp_local(0.5), mesh=mesh)


# ---------------------------------------------------------------------------
# ledger: calibrate-then-resume composes onto the restored spend
# ---------------------------------------------------------------------------

def test_calibrate_then_resume_composes_budget(toy, key, tmp_path):
    """Engine.fit double-advance fix: calibration happens AFTER the resume
    branch, for the remaining rounds only, composed on the ledger's restored
    spend — the whole 20-round trajectory lands exactly on the (raised)
    resume budget instead of overshooting it."""

    def make(sigma):
        strat = _dp_local(sigma)
        ledger = PrivacyLedger(sigma=sigma, delta=1e-3, sample_rate=0.25)
        eng = Engine(strat, eval_every=5, checkpoint_dir=str(tmp_path),
                     ledger=ledger)
        return eng, strat

    eng, strat = make(1.0)
    eng.fit(toy, rounds=10, key=key, batch_size=8, target_epsilon=8.0)
    sigma1 = strat.sigma
    assert abs(eng.ledger.epsilon() - 8.0) < 1e-6

    eng2, strat2 = make(sigma1)   # resume at the σ the first run trained with
    _, hist = eng2.fit(toy, rounds=20, key=key, batch_size=8, resume=True,
                       target_epsilon=12.0)
    assert eng2.ledger.rounds_seen == 20
    # rounds 0..10 restored at σ1 already spent ε=8; the recalibrated σ fits
    # rounds 10..20 into the remaining budget. Pre-fix, calibration ran
    # before the resume advanced start_round (sizing σ for 20 fresh rounds)
    # and ignored the restored spend — the trajectory missed the target.
    assert abs(hist.metrics["dp_epsilon"][-1] - 12.0) < 1e-6
    # the recalibration solved a different problem than run 1's (compose onto
    # ε=8 of restored spend), so it found a different σ
    assert abs(strat2.sigma - sigma1) > 1e-3


# ---------------------------------------------------------------------------
# host-mesh clamping (pure) + client-mesh construction
# ---------------------------------------------------------------------------

def test_host_mesh_shape_explicit_clamping():
    assert host_mesh_shape(4, 2, 8) == (4, 2)
    assert host_mesh_shape(16, 16, 8) == (8, 1)    # data eats every device
    assert host_mesh_shape(3, 4, 8) == (3, 2)      # model fits what's left
    assert host_mesh_shape(0, 4, 8) == (1, 4)      # no n//0 crash
    assert host_mesh_shape(2, 0, 8) == (2, 1)
    assert host_mesh_shape(5, 5, 1) == (1, 1)
    assert host_mesh_shape(1, 1, 0) == (1, 1)
    d, m = host_mesh_shape(3, 3, 8)
    assert d * m <= 8 and d >= 1 and m >= 1


def test_make_host_mesh_on_real_devices():
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(4, 4)   # clamps to whatever the host has
    n = len(jax.devices())
    d, m = mesh.shape["data"], mesh.shape["model"]
    assert (d, m) == host_mesh_shape(4, 4, n)


def test_make_client_mesh_shape():
    mesh = make_client_mesh()
    assert tuple(mesh.shape.keys()) == ("clients",)
    assert mesh.shape["clients"] == len(jax.devices())
    assert make_client_mesh(1).shape["clients"] == 1


def test_client_mesh_axes_are_auto_and_devolve_cleanly():
    """Every mesh builder uses Auto axes. Under jax's default Explicit axes a
    client stack moved off the mesh onto one device (what
    ``ShardedEngine._finalize_state`` does before eval) keeps ``@clients`` in
    its type, and vmapping it beside an unsharded array fails."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec
    from repro.config import MeshConfig
    from repro.launch.mesh import make_host_mesh, make_mesh
    mesh = make_client_mesh()
    for m in (mesh, make_host_mesh(1, 1),
              make_mesh(MeshConfig(data=1, model=1))):
        assert set(m.axis_types) == {AxisType.Auto}, m
    n = mesh.shape["clients"]
    x = jax.device_put(jnp.arange(8.0 * n).reshape(4 * n, 2),
                       NamedSharding(mesh, PartitionSpec("clients")))
    x1 = jax.device_put(x, jax.devices()[0])
    out = jax.jit(jax.vmap(lambda a, b: a * b))(x1, jnp.ones((4 * n, 2)))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
