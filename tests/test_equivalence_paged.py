"""Equivalence tier, family ``paged``: ``PagedEngine`` ≡ the resident
``Engine`` (the paged ≡ resident tier).

The scenarios run once, in one 8-fake-device subprocess
(``_sharded_equivalence_main.py paged``); each test asserts the records
of its scenarios, so a failure names the exact scenario."""
import pytest

from _sharded_equivalence_main import (STATE_ULPS,
                                       assert_bit_exact as _assert_bit_exact,
                                       run_family)

TIMEOUT = 900   # seconds for the family's subprocess


@pytest.fixture(scope="module")
def equivalence():
    return run_family("paged", timeout=TIMEOUT)


@pytest.mark.slow
def test_paged_engine_bit_exact(equivalence):
    """The paged ≡ resident tier: the host-resident population with paged
    cohorts reproduces the resident engine bit-exactly — final state AND
    full History (accuracy + every metric) — for p4 / fedavg / dp_dsgt
    across full / sampling / async schedules, including uneven cohort sizes
    (M=6 fixed-k, Bernoulli draws) and a non-ring gossip graph whose
    in-neighbor closure the cohort planner must page in."""
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
