"""Equivalence tier, family ``schedules``: the strategies (Local, FedAvg
with the gather and psum reductions, Scaffold, ProxyFL, DP-DSGT, P4) under
the three schedules, on even and uneven client counts, sharded ≡
single-device, and the telemetry-off contract on the sharded path.

The scenarios run once, in one 8-fake-device subprocess
(``_sharded_equivalence_main.py schedules``); each test asserts the records
of its scenarios, so a failure names the exact scenario."""
import pytest

from _sharded_equivalence_main import (P4_GATHER_STATE_ULPS, STATE_ULPS,
                                       assert_bit_exact as _assert_bit_exact,
                                       run_family)

TIMEOUT = 900   # seconds for the family's subprocess


@pytest.fixture(scope="module")
def equivalence():
    return run_family("schedules", timeout=TIMEOUT)


@pytest.mark.slow
def test_subprocess_saw_eight_devices(equivalence):
    assert equivalence["devices"] == 8


@pytest.mark.slow
def test_full_participation_bit_exact_histories(equivalence):
    """Sharded FullParticipation histories (and states, where the backend's
    fusion allows) are bit-exact vs the single-device engine for p4 / fedavg
    (gather reduction) / dp_dsgt."""
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
    """The default psum-tree cohort mean is bit-close to both the single-device
    engine and the gather path on the same mesh."""
    for name in ("fedavg_psum_full", "fedavg_psum_sampling"):
        rec = equivalence[name]
        assert rec["rounds_equal"] and rec["accuracy_maxdiff"] < 1e-5, rec
        assert rec["state_maxdiff"] < 1e-5, (name, rec)
    rec = equivalence["fedavg_psum_vs_gather"]
    assert rec["rounds_equal"] and rec["state_maxdiff"] < 1e-5, rec


@pytest.mark.slow
def test_scaffold_proxyfl_sharded_ports(equivalence):
    """Scaffold and ProxyFL run under the ShardedEngine — bit-exact vs
    single-device, including the mixed stacked/replicated Scaffold carry and
    uneven padding."""
    for name in ("scaffold_full", "scaffold_sampling", "scaffold_uneven"):
        _assert_bit_exact(equivalence[name])
    for name in ("proxyfl_full", "proxyfl_uneven"):
        _assert_bit_exact(equivalence[name], STATE_ULPS)


@pytest.mark.slow
def test_telemetry_off_and_tap_on_equivalence(equivalence):
    """The zero-overhead-off contract on the sharded path: a disabled Telemetry
    builds the unchanged chunk-cache key and is bit-exact with no telemetry
    at all — and an ENABLED tap is bit-exact too, because the sharded trace
    stays tap-free (per-round events stream host-side from the stacked chunk
    outputs, covering every round exactly once)."""
    rec = equivalence["telemetry_off_sharded"]
    assert rec["chunk_key_unchanged"], rec
    assert rec["rounds_equal"] and rec["accuracy_bit_equal"], rec
    assert rec["state_bit_equal"], rec
    assert rec["tap_rounds"] == list(range(8)), rec
