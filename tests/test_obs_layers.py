"""Named layers of the round step, the op -> layer table read from the
compiled chunk, program spans on the profiler's timeline, and the
``jax.compile`` probe."""
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import (DPConfig, P4Config, RunConfig, ScheduleConfig,
                          TrainConfig)
from repro.core.p4 import P4Strategy, P4Trainer
from repro.engine import (CHUNK_STATS, Engine, FederatedData, make_schedule)
from repro.engine.loop import CHUNK_CACHE
from repro.obs import (LAYERS, Telemetry, get_probe, hlo_layers, layer,
                       op_layers, span)
from repro.obs import layers as layers_mod
from repro.obs.layers import MODEL_LAYERS
from repro.obs import spans as spans_mod

M, FEAT, CLASSES, N, B = 8, 12, 3, 16, 8


def _data():
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(M, N, FEAT)).astype(np.float32)
    ys = rng.integers(0, CLASSES, size=(M, N)).astype(np.int32)
    return FederatedData(jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(xs),
                         jnp.asarray(ys))


def _program(kind="full", chunk=4, lr=0.5):
    cfg = RunConfig(
        dp=DPConfig(epsilon=15.0, rounds=10, sample_rate=0.5, clip_norm=1.0,
                    per_example_chunk=chunk),
        p4=P4Config(group_size=4, sample_peers=7),
        train=TrainConfig(learning_rate=lr),
        schedule=ScheduleConfig(kind=kind, client_rate=0.5, mode="fixed",
                                accountant="none"))
    trainer = P4Trainer(feat_dim=FEAT, num_classes=CLASSES, cfg=cfg)
    strategy = P4Strategy(trainer=trainer)
    strategy.set_groups([[0, 1, 2, 3], [4, 5, 6, 7]], M)
    return trainer, strategy, make_schedule(cfg.schedule)


def _one_chunk(trainer, strategy, schedule, **kw):
    data = _data()
    engine = Engine(strategy, eval_every=2, schedule=schedule, **kw)
    state = trainer.init_clients(jax.random.PRNGKey(0), M)
    state, _, _ = engine.run_rounds(state, data, jax.random.PRNGKey(1), 0, 2,
                                    B)
    jax.block_until_ready(state)
    return engine, state, data


# ---------------------------------------------------------------- layers

@pytest.mark.parametrize("kind,chunk", [("full", 4), ("sampling", 4),
                                        ("full", 0)])
def test_op_layer_table_names_every_layer(kind, chunk):
    _one_chunk(*_program(kind, chunk))
    table = op_layers()
    seen = {name for layers in table.values() for name in layers}
    # a language model's own layers have a test of their own below
    assert seen == set(LAYERS) - set(MODEL_LAYERS)
    for layers in table.values():
        assert len(set(layers)) == len(layers)
        for inner in ("per_example_grads", "dp_clip", "dp_noise",
                      "private_grad", "proxy_dp_grad", "metrics_step"):
            if inner in layers and "local_update" in layers:
                assert layers.index("local_update") < layers.index(inner)
    # the DP pipeline's device ops sit under the client step
    for inner in ("per_example_grads", "dp_clip"):
        ops = [ls for ls in table.values() if inner in ls]
        assert ops and any(ls[0] == "local_update" for ls in ops)
        assert all("proxy_dp_grad" in ls for ls in ops
                   if ls[0] == "local_update")


def test_layer_rejects_unknown_names():
    with pytest.raises(ValueError, match="unknown layer"):
        layer("local_updates")
    with layer("aggregate"):
        pass


def test_hlo_layers_on_a_synthetic_module():
    text = """HloModule jit_run

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %sine.1 = f32[4]{0} sine(%param_0), metadata={op_name="jit(run)/while/body/local_update/vmap(per_example_grads)/sin"}
}

ENTRY %main.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(run)/while/body/local_update/vmap(per_example_grads)/sin"}
  %copy.130 = f32[4]{1,0} copy(%fusion.3)
  %mul.2 = f32[4]{0} multiply(%copy.130, %copy.130), metadata={op_name="jit(run)/local_update/transpose(jvp(private_grad))/mul"}
  %copy.9 = f32[4]{0} copy(%p)
  %add.4 = f32[4]{0} add(%copy.9, %mul.2), metadata={op_name="jit(run)/jit(aggregate)/add"}
  %neg.5 = f32[4]{0} negate(%add.4), metadata={op_name="jit(run)/local_update_x/aggregate/neg"}
  %orphan.6 = f32[4]{0} copy(%p)
  ROOT %tuple.7 = (f32[4]{0}) tuple(%neg.5)
}
"""
    table = hlo_layers(text)
    assert table["fusion.3"] == ("local_update", "per_example_grads")
    assert table["sine.1"] == ("local_update", "per_example_grads")
    # a layout copy without op_name takes its operand's layers
    assert table["copy.130"] == ("local_update", "per_example_grads")
    # scopes inside transform wrappers match
    assert table["mul.2"] == ("local_update", "private_grad")
    # a jitted function's name is not a scope; a partial word is no layer
    assert table["add.4"] == ()
    assert table["neg.5"] == ("aggregate",)
    # no operand with an op_name: the nearest user's layers
    assert table["copy.9"] == ()
    assert table["p"] == ("local_update", "per_example_grads")
    # reached by neither: left out
    assert "orphan.6" not in table


def test_building_the_table_compiles_nothing_and_leaves_probes():
    trainer, strategy, schedule = _program("full", 4, lr=0.25)
    engine, state, data = _one_chunk(trainer, strategy, schedule)
    events = []

    def listen(event, *_, **__):
        events.append(event)
    jax.monitoring.register_event_listener(listen)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        cache_before = list(CHUNK_CACHE)
        stats_before = dict(CHUNK_STATS)
        compile_before = get_probe("jax.compile").snapshot()
        layers_mod._LAST[0].table = None
        table = op_layers()
        assert table
        assert [e for e in events if "/jax/core/compile" in e] == []
        assert list(CHUNK_CACHE) == cache_before
        assert dict(CHUNK_STATS) == stats_before
        assert get_probe("jax.compile").snapshot() == compile_before
        assert op_layers() is table            # cached per executable
        # and the engine still runs the same executable: no retrace
        engine.run_rounds(state, data, jax.random.PRNGKey(1), 2, 4, B)
        assert dict(CHUNK_STATS)["traces"] == stats_before["traces"]
    finally:
        jax.monitoring.unregister_event_listener(listen)
        jax.monitoring.unregister_event_duration_listener(listen)


def test_first_dispatch_records_without_a_second_trace_or_compile():
    trainer, strategy, schedule = _program("full", 4, lr=0.125)
    before = dict(CHUNK_STATS)
    compiles = get_probe("jax.compile").snapshot()
    _one_chunk(trainer, strategy, schedule)
    assert CHUNK_STATS["traces"] - before["traces"] == 1
    after = get_probe("jax.compile").snapshot()
    assert after["compiles"] > compiles["compiles"]
    assert after["last_compile"] == "jit(run)"


# ------------------------------------------------------------ compile probe

def test_compile_probe_counts_where_compiles_happen():
    probe = get_probe("jax.compile")
    before = probe.snapshot()

    def probe_target(x):
        return jnp.sin(x) * 3.0

    jax.jit(probe_target)(jnp.ones((3, 5))).block_until_ready()
    d = probe.delta_from(before)
    assert d["traces"] >= 1 and d["lowerings"] >= 1 and d["compiles"] >= 1
    assert d["compile_s"] > 0
    assert d["last_trace"] == "probe_target"
    assert d["last_compile"] == "jit(probe_target)"


def test_chunk_span_reports_the_chunks_compiles(tmp_path):
    run_dir = str(tmp_path / "run")
    tel = Telemetry(run_dir)
    _one_chunk(*_program("full", 4, lr=0.0625), telemetry=tel)
    tel.close()
    with open(os.path.join(run_dir, "events.jsonl")) as f:
        events = [json.loads(line) for line in f if line.strip()]
    chunk = [e for e in events if e.get("name") == "engine.run_rounds"]
    assert len(chunk) == 1 and chunk[0]["traced"] is True
    assert chunk[0]["compiles"] >= 1 and chunk[0]["compile_s"] > 0
    assert chunk[0]["compiled"] == "jit(run)"
    # the linear step traced the stacked affine step, whose proxy takes the
    # closed form, and no other route
    assert set(chunk[0]["dp_path"]) == {"affine_closed_form",
                                        "affine_stacked"}


# ------------------------------------------------------------------ spans

def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "id" in stats:
                    out.append((e.name, e.start_ns, e.duration_ns, stats))
    return out


def test_program_spans_on_the_profilers_timeline(tmp_path):
    trainer, strategy, schedule = _program("full", 4, lr=0.03125)
    run_dir = str(tmp_path / "run")
    tel = Telemetry(run_dir)
    data = _data()
    engine = Engine(strategy, eval_every=2, schedule=schedule, telemetry=tel)
    state = trainer.init_clients(jax.random.PRNGKey(0), M)
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        state, _, _ = engine.run_rounds(state, data, jax.random.PRNGKey(1),
                                        0, 2, B)
        acc = trainer.evaluate(state, data.test_x, data.test_y)
        groups = trainer.form_groups(state, seed=0)
        jax.block_until_ready(acc)
    finally:
        jax.profiler.stop_trace()
    tel.close()
    assert sorted(sum(groups, [])) == list(range(M))

    host = _host_events(trace_dir)
    by_id = {s["id"]: (name, t0, dt, s) for name, t0, dt, s in host}
    names = {name for name, *_ in host}
    assert {"engine.run_rounds", "engine.chunk_lookup", "engine.dispatch",
            "p4.evaluate", "p4.form_groups", "p4.l1_distance",
            "p4.greedy"} <= names
    assert not names & set(spans_mod.RESERVED)

    def parent_of(name):
        (sid,) = [s["id"] for n, _, _, s in host if n == name]
        return by_id.get(by_id[sid][3]["parent"], (None,))[0]

    assert parent_of("engine.chunk_lookup") == "engine.run_rounds"
    assert parent_of("engine.dispatch") == "engine.run_rounds"
    assert parent_of("p4.l1_distance") == "p4.form_groups"
    assert parent_of("p4.greedy") == "p4.form_groups"
    # a child lies inside its parent on the timeline
    for name, t0, dt, s in host:
        if s["parent"] in by_id:
            _, p0, pdt, _ = by_id[s["parent"]]
            assert p0 <= t0 and t0 + dt <= p0 + pdt
    run = [s for n, _, _, s in host if n == "engine.run_rounds"][0]
    assert all(s["chunk"] == run["chunk"] for n, _, _, s in host
               if n.startswith("engine."))

    # the engine's spans carry the same ids in events.jsonl
    with open(os.path.join(run_dir, "events.jsonl")) as f:
        events = [json.loads(line) for line in f if line.strip()]
    logged = {e["id"]: e for e in events if e["type"] == "span"}
    engine_spans = {s["id"]: (n, s) for n, _, _, s in host
                    if n.startswith("engine.")}
    assert set(engine_spans) == set(logged)
    for sid, (name, s) in engine_spans.items():
        assert logged[sid]["name"] == name
        assert logged[sid]["parent"] == s["parent"]
        assert logged[sid]["chunk"] == s["chunk"]


def test_spans_without_profiler_or_telemetry_record_nothing():
    first = next(spans_mod._IDS)
    chunk = spans_mod._CHUNK[0]
    with span("engine.run_rounds", chunk=True) as outer:
        with span("engine.dispatch") as inner:
            assert spans_mod._stack() == []
    assert outer.id is None and inner.id is None and outer.dt is None
    assert next(spans_mod._IDS) == first + 1
    assert spans_mod._CHUNK[0] == chunk


@pytest.mark.parametrize("name", ["window", "dispatch", "eval", "sync"])
def test_span_names_of_the_harness_are_refused(name):
    with pytest.raises(ValueError, match="reserved"):
        span(name)
