"""Equivalence tier, family ``topology``: DP-DSGT's gossip over non-ring,
banded, learned and shard-resident graphs, with i.i.d. link faults and the
correlated fault regimes, sharded ≡ single-device.

The scenarios run once, in one 8-fake-device subprocess
(``_sharded_equivalence_main.py topology``); each test asserts the records
of its scenarios, so a failure names the exact scenario."""
import pytest

from _sharded_equivalence_main import run_family

TIMEOUT = 900   # seconds for the family's subprocess


@pytest.fixture(scope="module")
def equivalence():
    return run_family("topology", timeout=TIMEOUT)


@pytest.mark.slow
def test_dsgt_topology_equivalence(equivalence):
    """Sharded ≡ single-device for a non-ring topology (4-regular expander,
    gossip-matching sequence) and the shard-resident slice-local mixing
    path."""
    for name in ("dsgt_topology_expander", "dsgt_gossip_sequence",
                 "dsgt_topology_resident"):
        rec = equivalence[name]
        assert rec["rounds_equal"] and rec["accuracy_bit_equal"], (name, rec)
        assert rec["state_maxdiff"] < 1e-6, (name, rec)


@pytest.mark.slow
def test_dsgt_faulty_topology_equivalence(equivalence):
    """A faulty run (drop probability > 0) — the in-jit fault draws are
    replicated, so every slice realizes the same topology."""
    for name in ("dsgt_topology_faulty", "dsgt_topology_resident_faulty"):
        rec = equivalence[name]
        assert rec["rounds_equal"] and rec["accuracy_maxdiff"] < 1e-5, (name,
                                                                        rec)
        assert rec["state_maxdiff"] < 1e-6, (name, rec)


@pytest.mark.slow
def test_dsgt_learned_pushsum_equivalence(equivalence):
    """Learned directed graphs (column-stochastic W, push-sum weight scalar
    riding the x mix as a joint leaf) stay sharded ≡ single-device — static
    estimate, a two-estimate time-varying window, and a faulted run (the
    sender-side diagonal fold keeps the realized matrix column-stochastic)."""
    for name in ("dsgt_learned_pushsum", "dsgt_learned_timevarying"):
        rec = equivalence[name]
        assert rec["rounds_equal"] and rec["accuracy_bit_equal"], (name, rec)
        assert rec["state_maxdiff"] < 1e-6, (name, rec)
    rec = equivalence["dsgt_learned_faulty"]
    assert rec["rounds_equal"] and rec["accuracy_maxdiff"] < 1e-5, rec
    assert rec["state_maxdiff"] < 1e-6, rec


@pytest.mark.slow
def test_banded_topologies_gather_free(equivalence):
    """Banded/bounded-bandwidth graphs (ring, faulty ring, keep-masked ring,
    torus, circulant expander) never fall back to the all_gather mixing path
    — the collective probe records only halo ppermutes for them (per chunk
    trace, so 0 gathers is 0 outright)."""
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
    """Keep-masked / i.i.d.-faulty rings and the torus route through the halo
    path AND stay equivalent to single-device."""
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
def test_correlated_fault_regimes_equivalence(equivalence):
    """Sharded ≡ single-device under the stateful fault chains — the
    ``FaultState`` carry is replicated, so every slice steps the identical
    Gilbert–Elliott / churn / partition realization."""
    for name in ("dsgt_fault_burst", "dsgt_fault_churn",
                 "dsgt_fault_partition"):
        rec = equivalence[name]
        assert rec["rounds_equal"] and rec["accuracy_bit_equal"], (name, rec)
        assert rec["state_maxdiff"] < 1e-6, (name, rec)
