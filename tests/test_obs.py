"""Observability subsystem: probe-registry semantics, ``History.record``
validation, the telemetry event-log/manifest contract (JSONL trajectory ≡
returned History), the in-jit tap's bit-exactness, the zero-overhead-off
guarantee, and the monitor CLI."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.baselines.fedavg import FedAvgStrategy
from repro.baselines.local import LocalStrategy
from repro.engine import (CHUNK_STATS, Engine, FederatedData, History,
                          PrivacyLedger, clear_chunk_cache)
from repro.launch import monitor
from repro.obs import (Probe, ProbeRegistry, REGISTRY, Telemetry, get_probe,
                       probe_deltas)
from repro.topology.mixing import MIX_STATS


@pytest.fixture(scope="module")
def toy():
    rng = np.random.default_rng(0)
    M, feat, classes, n = 6, 16, 3, 48
    protos = rng.normal(size=(classes, feat)).astype(np.float32) * 3
    ys = rng.integers(0, classes, size=(M, n)).astype(np.int32)
    xs = protos[ys] + rng.normal(size=(M, n, feat)).astype(np.float32) * 0.4
    return FederatedData(xs, ys, jnp.asarray(xs), jnp.asarray(ys))


def _strategy():
    return FedAvgStrategy(feat_dim=16, num_classes=3, lr=0.5, clip=1.0,
                          sigma=0.7)


# ---------------------------------------------------------------------------
# probe registry
# ---------------------------------------------------------------------------

def test_probe_keeps_plain_dict_semantics():
    reg = ProbeRegistry()
    p = Probe("t.counters", {"hits": 0, "seconds": 0.0}, registry=reg)
    p["hits"] += 3
    p.update(seconds=1.5)
    assert dict(p) == {"hits": 3, "seconds": 1.5}
    assert reg.get("t.counters") is p
    assert reg.snapshot()["t.counters"] == {"hits": 3, "seconds": 1.5}
    p["late_key"] = 7          # keys born after construction reset to int 0
    p.reset()
    assert dict(p) == {"hits": 0, "seconds": 0.0, "late_key": 0}
    assert isinstance(p["seconds"], float)


def test_probe_deltas_nest_and_freeze():
    reg = ProbeRegistry()
    p = Probe("t.nest", {"n": 0}, registry=reg)
    p["n"] += 100              # pre-scope counts must not leak into deltas
    with reg.deltas("t.nest") as outer:
        p["n"] += 1
        with reg.deltas("t.nest") as inner:
            p["n"] += 2
            assert inner["t.nest"]["n"] == 2    # live read inside the scope
        assert inner["t.nest"]["n"] == 2        # frozen at scope exit
        assert outer["t.nest"]["n"] == 3
    p["n"] += 50
    assert outer["t.nest"]["n"] == 3            # outer froze at its own exit
    with pytest.raises(KeyError):
        reg.deltas("t.missing").__enter__()


def test_legacy_stats_dicts_are_registered_probes():
    # the module-global aliases remain the increment idiom; the registry
    # sees every mutation without the owners changing their code
    assert get_probe("engine.chunk_cache") is CHUNK_STATS
    assert get_probe("topology.mix") is MIX_STATS
    with probe_deltas("topology.mix", "engine.chunk_cache") as d:
        MIX_STATS["calls"] += 4
        CHUNK_STATS["hits"] += 1
    assert d["topology.mix"]["calls"] == 4
    assert d["engine.chunk_cache"]["hits"] == 1
    MIX_STATS["calls"] -= 4    # leave the process-lifetime counters as found
    CHUNK_STATS["hits"] -= 1


def test_subsystem_probes_registered_on_import():
    import repro.engine.population    # noqa: F401
    import repro.kernels.dispatch     # noqa: F401
    import repro.resilience           # noqa: F401
    for name in ("engine.prefetch", "kernels.autotune", "resilience.faults"):
        assert name in REGISTRY.names()


# ---------------------------------------------------------------------------
# History.record validation
# ---------------------------------------------------------------------------

def test_history_record_accepts_scalars_and_0d_arrays():
    h = History()
    h.record(0, 0.5, {"a": 1, "b": 2.5, "c": np.float32(3.0),
                      "d": np.asarray(4.0), "e": jnp.asarray(5.0),
                      "f": True})
    assert h.accuracy == [0.5]
    assert h.metrics["d"] == [4.0] and h.metrics["e"] == [5.0]
    assert h.metrics["f"] == [1.0]


def test_history_record_rejects_non_scalars_naming_the_key():
    h = History()
    with pytest.raises(TypeError, match="'grad_norm'.*shape \\(1,\\)"):
        h.record(0, 0.5, {"grad_norm": np.ones((1,))})
    with pytest.raises(TypeError, match="'accuracy'"):
        h.record(0, np.ones((3,)))


# ---------------------------------------------------------------------------
# telemetry: event log / manifest / tap
# ---------------------------------------------------------------------------

def _events(run_dir):
    with open(os.path.join(run_dir, "events.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


def _fit(toy, telemetry=None, rounds=8):
    eng = Engine(_strategy(), eval_every=2, telemetry=telemetry,
                 ledger=PrivacyLedger(sigma=0.7, delta=1e-5))
    return eng.fit(toy, rounds=rounds, key=jax.random.PRNGKey(3),
                   batch_size=8)


def test_tap_event_log_matches_returned_history(toy, tmp_path):
    run_dir = str(tmp_path / "run")
    tel = Telemetry(run_dir, tap=True)
    rounds = 8
    _, hist = _fit(toy, telemetry=tel, rounds=rounds)
    tel.close()

    events = _events(run_dir)
    evals = [e for e in events if e["type"] == "eval"]
    assert [e["round"] for e in evals] == hist.rounds
    assert [e["accuracy"] for e in evals] == pytest.approx(hist.accuracy)
    assert ([e["dp_epsilon"] for e in evals]
            == pytest.approx(hist.metrics["dp_epsilon"]))

    # the tap streamed every scanned round exactly once, σ included
    taps = [e for e in events if e["type"] == "tap"]
    assert sorted(e["round"] for e in taps) == list(range(rounds))
    assert all(e["sigma"] == pytest.approx(0.7) for e in taps)

    manifest = json.load(open(os.path.join(run_dir, "manifest.json")))
    assert manifest["phases"][0]["engine"] == "Engine"
    assert manifest["phases"][0]["strategy"] == "FedAvgStrategy"
    assert ([t["round"] for t in manifest["trajectory"]]
            == [e["round"] for e in evals])
    assert "engine.chunk_cache" in manifest["probes"]

    # chunk spans carry the trace-vs-execute split read off the probe
    chunks = [e for e in events
              if e["type"] == "span" and e["name"] == "engine.run_rounds"]
    assert chunks and chunks[0]["traced"] is True


def test_tap_on_history_is_bit_exact_with_tap_off(toy):
    state_off, hist_off = _fit(toy)
    tel = Telemetry(None, tap=True)     # disabled: run_dir=None
    state_dis, hist_dis = _fit(toy, telemetry=tel)
    assert hist_off.accuracy == hist_dis.accuracy
    for a, b in zip(jax.tree_util.tree_leaves(state_off),
                    jax.tree_util.tree_leaves(state_dis)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_tap_on_results_bit_exact_with_enabled_telemetry(toy, tmp_path):
    state_off, hist_off = _fit(toy)
    tel = Telemetry(str(tmp_path / "run"), tap=True)
    state_on, hist_on = _fit(toy, telemetry=tel)
    tel.close()
    assert hist_off.accuracy == hist_on.accuracy
    assert hist_off.metrics == hist_on.metrics
    for a, b in zip(jax.tree_util.tree_leaves(state_off),
                    jax.tree_util.tree_leaves(state_on)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_telemetry_off_is_provably_free(toy):
    strategy = _strategy()
    plain = Engine(strategy, eval_every=2)
    k = plain._chunk_key(8, 8)
    assert k == Engine(strategy, eval_every=2,
                       telemetry=Telemetry(None))._chunk_key(8, 8)
    assert k == Engine(strategy, eval_every=2,
                       telemetry=Telemetry(None, tap=True))._chunk_key(8, 8)

    # a disabled-telemetry engine must reuse the warm compiled chunk
    clear_chunk_cache()
    plain.fit(toy, rounds=4, key=jax.random.PRNGKey(0), batch_size=8,
              evaluate=False)
    with probe_deltas("engine.chunk_cache") as d:
        Engine(strategy, eval_every=2,
               telemetry=Telemetry(None, tap=True)).fit(
                   toy, rounds=4, key=jax.random.PRNGKey(0), batch_size=8,
                   evaluate=False)
    assert d["engine.chunk_cache"]["traces"] == 0
    assert d["engine.chunk_cache"]["hits"] > 0

    # ... while a *tapped* chunk is a different traced computation
    tapped = Engine(strategy, eval_every=2,
                    telemetry=Telemetry("/tmp/ignored", tap=True))
    assert tapped._chunk_key(8, 8) != k


def test_monitor_summarize_and_tail(toy, tmp_path):
    run_dir = str(tmp_path / "run")
    tel = Telemetry(run_dir, tap=True)
    _fit(toy, telemetry=tel)
    tel.close()

    text = monitor.summarize(run_dir)
    assert "phase 0: Engine/FedAvgStrategy" in text
    assert "span engine.run_rounds:" in text
    assert "tap: 8 rounds streamed [0..7]" in text
    assert "trajectory:" in text

    lines = [monitor._fmt_event(e) for e in monitor.load_events(run_dir)]
    assert any(line.startswith("tap") for line in lines)
    assert any(line.startswith("eval") for line in lines)

    # empty dir degrades gracefully
    assert "no telemetry found" in monitor.summarize(str(tmp_path / "void"))
