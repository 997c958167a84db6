"""Equivalence tier, family ``p4``: P4's group layouts, its fault-injected
and failover rounds, straggler chains, the whole trainer pipeline and the
byte accounting of absent clients, sharded ≡ single-device.

The scenarios run once, in one 8-fake-device subprocess
(``_sharded_equivalence_main.py p4``); each test asserts the records
of its scenarios, so a failure names the exact scenario."""
import pytest

from _sharded_equivalence_main import (P4_GATHER_STATE_ULPS, STATE_ULPS,
                                       assert_bit_exact as _assert_bit_exact,
                                       run_family)

TIMEOUT = 900   # seconds for the family's subprocess


@pytest.fixture(scope="module")
def equivalence():
    return run_family("p4", timeout=TIMEOUT)


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
