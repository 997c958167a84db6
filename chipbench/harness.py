"""One run of one benchmark cell: set-up, the measured window, the optional
trace of a slice of it, the comparison with the plain reference, and the
result line.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration file it names, ``traffic/<traffic>.json``,
``limits/<cell>.json``, ``metrics/<metric>.py`` for each per-layer metric,
and by the configuration's ``model``, ``models/<model>.py`` (its data, the
program's model, the reference's model and its correct count; see
``models/__init__.py``) and ``counts/<model>.py`` (its work). Adding a cell,
a configuration or a metric adds files and entries; a new kind of model
adds ``models/<model>.py`` and ``counts/<model>.py`` beside them. No file
here changes.

What a run holds on the device, whatever the model's size: the program's
state, its data and what its own steps need; the run's before-states (the
initial weights, the state before the first chunk) live on the host, and
the changes are read from them one leaf at a time. After the window the
reference holds one donated state and one block of ``reference_block``
clients, each stepping one block of ``reference_example_block`` examples
at a time (an optional key of the configuration; without it a client's
whole batch is one block). That bounds the states and the examples, not
the temporaries of one model's size that a compiled round keeps beside
them (gradients, noise, layout copies). A federation of one client
(``clients`` 1) is a valid cell: one group, no pair of distances to
compare.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, counts, program as prog_lib
from chipbench import reference as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACED_CHUNKS = 2


class NoChip(RuntimeError):
    """The cell's chips are not there: the run prints no result."""


# ------------------------------------------------------------------ spec

def _applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Dict[str, Any]:
    """The cell's entry of BENCHMARK.json with its configuration, model
    kind, traffic, limits and metric entries resolved by name."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = dict(cells[name])
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[cell["config"]]["file"])) as f:
        cell["cfg"] = json.load(f)
    here = os.path.join(root, "chipbench")
    with open(os.path.join(here, "traffic", cell["traffic"] + ".json")) as f:
        cell["mix"] = json.load(f)
    with open(os.path.join(here, "limits", name + ".json")) as f:
        cell["limits"] = json.load(f)["limits"]
    cell["end_to_end"] = [m for m in bench["end_to_end"]
                          if _applies(m, name)]
    cell["per_layer"] = [m for m in bench["per_layer"] if _applies(m, name)]
    cell["root"] = root
    cell["kind"] = model_kind(cell["cfg"], root)
    return cell


def model_kind(cfg, root: str = ROOT):
    """The configuration's model kind, ``models/<model>.py``."""
    return load_module(os.path.join(root, "chipbench", "models",
                                    cfg["model"] + ".py"))


@functools.cache
def load_module(path: str):
    """A benchmark module by its file path (model kinds, metric readers,
    work counts), loaded once: jitted functions that take a model kind as a
    static argument find their compiled programs again."""
    name = "chipbench_" + os.path.relpath(path, ROOT).replace(os.sep, "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric_name: str, root: str = ROOT):
    return load_module(os.path.join(root, "chipbench", "metrics",
                                    metric_name + ".py")).read


def peaks_for(kind: str):
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


def hbm_peak(stats) -> int:
    """A chip's peak HBM footprint from its allocator's statistics: the peak
    of live buffers plus the peak of the memory reserved for the loaded
    executables' temporaries. The TPU runtime reserves an executable's
    temporaries apart from ``bytes_in_use`` when it loads it, so the live
    peak alone leaves out the per-example stacks and gathers of the step.
    The two peaks may fall at different times; their sum bounds the
    footprint from above, and is the room a cell needs to load."""
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def participants(mix) -> float:
    """Clients whose local update counts in a round."""
    M, s = mix["clients"], mix["schedule"]
    if s.get("kind", "full") != "sampling":
        return float(M)
    if s.get("mode", "bernoulli") == "fixed":
        return float(max(1, int(round(s["client_rate"] * M))))
    return float(s["client_rate"] * M)


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (wider than 32 bits)."""
    k = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


# ------------------------------------------------------------------ clocks

class CompileClock:
    """Seconds XLA spent producing executables (a backend compile, or a
    persistent-cache load when the entry is there), and how many."""

    def __init__(self):
        self.seconds, self.count, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


@dataclass
class Context:
    """What the per-layer readers read."""
    window: Dict[str, float]
    setup: Dict[str, float]
    trace: Optional[Dict[str, Any]]
    peaks: Optional[Dict[str, Any]]
    chips: int
    step_flops_per_example: int
    cfg: Dict[str, Any]
    mix: Dict[str, Any]


# ------------------------------------------------------------------ run

def run(cell, seed: int, seconds: float, trace: bool, *, t0: float,
        require_chip: bool = True, kernels: Optional[dict] = None,
        plant=None, log=print):
    """One run of ``cell`` (from ``load_cell``). Returns the result dict.
    ``kernels`` overrides the configuration's kernel block (a CPU rehearsal
    uses the reference backend); ``plant(program)`` breaks the program
    before set-up (the fault tests)."""
    cfg, mix = cell["cfg"], cell["mix"]
    devices = jax.devices()
    dev = devices[0]
    if require_chip:
        if dev.platform != "tpu":
            raise NoChip(f"no TPU: JAX reports platform {dev.platform!r}")
        if len(devices) < cell["chips"]:
            raise NoChip(f"the cell needs {cell['chips']} chips, JAX reports "
                         f"{len(devices)}")
    peaks = peaks_for(dev.device_kind) if require_chip else None
    clock = CompileClock()
    from repro.engine.loop import CHUNK_STATS

    E, B = mix["eval_every"], mix["local_batch"]
    nb = cfg["p4"]["bootstrap_rounds"]
    keys = run_keys(seed)

    # ---------------- set-up: data, bootstrap, Phase 1, warm-up chunk ----
    data = cell["kind"].make_data(mix, cfg, keys["data"], seed)
    p, fd, state, run_out, groups, grouping_s = first_chunk(
        cell, seed, keys, data, kernels=kernels, plant=plant)
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - t0
    setup = {"grouping_s": grouping_s, "compile_s": clock.seconds,
             "setup_s": setup_s}
    log(f"setup: {setup_s:.3f} s (compile {clock.seconds:.3f} s over "
        f"{clock.count} executables, {clock.hits} cache hits; grouping "
        f"{grouping_s:.3f} s; {len(groups)} groups)")

    # ---------------- the measured window --------------------------------
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    c0, k0 = clock.count, CHUNK_STATS["traces"]
    cursor, chunks, last_traced = nb + E, 0, 1 + TRACED_CHUNKS
    tw = time.perf_counter()
    while True:
        if trace and chunks == 1:
            jax.profiler.start_trace(trace_dir)
            traced_slice = jax.profiler.TraceAnnotation("window")
            traced_slice.__enter__()
        with jax.profiler.TraceAnnotation("dispatch"):
            state, _, _ = p.engine.run_rounds(state, fd, keys["cotrain"],
                                              cursor, cursor + E, B)
        with jax.profiler.TraceAnnotation("eval"):
            acc = p.trainer.evaluate(state, fd.test_x, fd.test_y)
            mean_acc = float(jnp.mean(acc))
        chunks += 1
        cursor += E
        if trace and chunks == last_traced:
            with jax.profiler.TraceAnnotation("sync"):
                jax.block_until_ready(state)
            traced_slice.__exit__(None, None, None)
            jax.profiler.stop_trace()
        if (time.perf_counter() - tw >= seconds
                and (not trace or chunks >= last_traced)):
            break
    jax.block_until_ready(state)
    window_s = time.perf_counter() - tw
    compiles = (clock.count - c0) + (CHUNK_STATS["traces"] - k0)
    stats = dev.memory_stats() or {}
    peak = max((hbm_peak(d.memory_stats() or {})
                for d in devices[:cell["chips"]]), default=0)
    del state
    rounds = chunks * E
    samples = rounds * participants(mix) * B * mix["local_steps"]
    window = {"seconds": window_s, "rounds": rounds, "samples": samples,
              "compiles": compiles}
    log(f"window: {chunks} chunks, {rounds} rounds in {window_s:.3f} s, "
        f"mean personalized accuracy {mean_acc:.4f} at round {cursor}, "
        f"{compiles} compiles; peak {peak} of {stats.get('bytes_limit')} B "
        f"(live {stats.get('peak_bytes_in_use')}, reserved "
        f"{stats.get('peak_bytes_reserved')})")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result_metrics, breakdown = {}, None
    if trace:
        from chipbench import trace_reduce
        red = trace_reduce.reduce_dir(trace_dir, window_annotation="window")
        shutil.rmtree(trace_dir, ignore_errors=True)
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["top_ops"],
                         "idle_gaps": red["idle_gaps"]}
        work = counts.step_flops_per_example(
            cfg, os.path.join(cell["root"], "chipbench", "counts"))
        ctx = Context(window=window, setup=setup, trace=red, peaks=peaks,
                      chips=cell["chips"], step_flops_per_example=work,
                      cfg=cfg, mix=mix)
        for m in cell["per_layer"]:
            v = reader(m["name"], cell["root"])(ctx)
            if v is not None:
                result_metrics[m["name"]] = {"value": float(v),
                                             "unit": m["unit"]}
    else:
        e2e = {"train_samples_per_s": samples / window_s,
               "peak_hbm_gib": peak / 2 ** 30, "setup_s": setup_s}
        for m in cell["end_to_end"]:
            result_metrics[m["name"]] = {"value": e2e[m["name"]],
                                         "unit": m["unit"]}

    # ---------------- the plain reference, after the window ---------------
    tr = time.perf_counter()
    nums = follow_reference(cell, data, keys, run_out, seed)
    ok, checks = compare.verdict(nums, cell["limits"])
    log(f"reference: {time.perf_counter() - tr:.3f} s")
    for k, v in nums.items():
        lim = cell["limits"].get(k)
        print(f"check {k}: {v!r} limit {lim!r}", file=sys.stderr)
    out = {"correct": bool(ok), "attempted": rounds, "failed": 0,
           "metrics": result_metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def run_keys(seed: int):
    base = seed_key(seed)
    return {k: jax.random.fold_in(base, i) for i, k in
            enumerate(("data", "init", "boot", "cotrain"), start=1)}


def first_chunk(cell, seed, keys, data, *, kernels=None, plant=None,
                keep=False):
    """The program from the seed through its bootstrap, Phase 1 and the
    first co-train chunk with its evaluation: the steps the reference
    follows. Returns the program, its data, the state after the chunk (to
    hand on to the window), what the comparison reads, the groups and the
    grouping seconds. The states the changes are read from are copied to
    the host, so the device holds one state; ``keep`` keeps both states of
    the chunk there (``run_out["states"]``)."""
    cfg, mix, kind = cell["cfg"], cell["mix"], cell["kind"]
    M, E, B = mix["clients"], mix["eval_every"], mix["local_batch"]
    nb = cfg["p4"]["bootstrap_rounds"]
    fd = prog_lib.federated(data)
    p = prog_lib.build(cfg, mix, kind.trainer_kwargs(cfg), kernels)
    if plant is not None:
        plant(p)
    shapes = prog_lib.state_shapes(p)
    if shapes != kind.param_shapes(cfg):
        raise RuntimeError(f"program model {shapes} differs from the "
                           f"configuration's {kind.param_shapes(cfg)}")
    state = ref.init_state(kind, cfg, M, keys["init"])
    init = host_copy(state)
    state, _, _ = p.bootstrap.run_rounds(state, fd, keys["boot"], 0, nb, None)
    run_out = {"boot_change": compare.change_norms(state, init)}
    del init
    tg = time.perf_counter()
    with prog_lib.seen_distances(run_out):
        groups = p.trainer.form_groups(state, seed)
    grouping_s = time.perf_counter() - tg
    run_out["groups"] = groups
    p.strategy.set_groups(groups, M)
    before = host_copy(state)
    state, metrics, _ = p.engine.run_rounds(state, fd, keys["cotrain"], nb,
                                            nb + E, B)
    acc = p.trainer.evaluate(state, fd.test_x, fd.test_y)
    float(jnp.mean(acc))
    run_out["change"] = compare.change_norms(state, before)
    if keep:
        run_out["states"] = (before, host_copy(state))
    del before
    run_out["correct"] = kind.run_correct(cfg, acc, data["test_y"])
    run_out["losses"] = np.stack([np.asarray(metrics["private_loss"]),
                                  np.asarray(metrics["proxy_loss"])], axis=1)
    return p, fd, state, run_out, groups, grouping_s


def reference_hp(cfg, mix):
    return {"lr": cfg["train"]["learning_rate"],
            "clip": cfg["dp"]["clip_norm"], "alpha": cfg["p4"]["alpha"],
            "beta": cfg["p4"]["beta"], "local_steps": mix["local_steps"]}


def reference_sigma(cfg, mix):
    dp = cfg["dp"]
    return ref.noble_sigma(dp["epsilon"], dp["delta"], dp["sample_rate"],
                           dp["rounds"], mix["local_steps"])


def follow_reference(cell, data, keys, run_out, seed):
    """The reference from the seed through the bootstrap, Phase 1 and the
    first co-train chunk, following the run's groups as a served model's
    reference follows its served tokens; the numbers of ``compare.numbers``
    for ``run_out``, and with ``run_out["states"]`` the diagnostic
    ``compare.diffs`` too. Phase 1 is judged in two parts: the run's
    distances against the reference's, and the run's groups against those
    the paper's greedy procedure forms on the run's own distances (the same
    matrix, so a near tie cannot fall differently)."""
    p4 = cell["cfg"]["p4"]
    keep = "states" in run_out
    refs = reference_outputs(cell, data, keys, dtype=jnp.float32,
                             fault=None, seed=seed, groups=run_out["groups"],
                             keep=keep)
    refs["groups"] = ref.greedy_groups(run_out["dist"], p4["group_size"],
                                       p4["sample_peers"], seed)
    nums = compare.numbers(run_out, refs)
    if keep:
        nums.update(compare.diffs(run_out["states"], refs["states"], refs))
    return nums


def reference_outputs(cell, data, keys, *, dtype, fault, seed,
                      groups=None, keep=False):
    """What the reference computes from the seed, in ``dtype``: the
    bootstrap's change, the Phase-1 distances, the first chunk's losses and
    change with ``groups`` (None: the groups it forms itself, acting as the
    program), and the test predictions it gets right. The rounds run on
    one device buffer; the states the changes are read from, and those
    ``keep`` keeps, are copied to the host first."""
    cfg, mix, kind = cell["cfg"], cell["mix"], cell["kind"]
    M, E, B = mix["clients"], mix["eval_every"], mix["local_batch"]
    nb, p4 = cfg["p4"]["bootstrap_rounds"], cfg["p4"]
    hp, sigma = reference_hp(cfg, mix), reference_sigma(cfg, mix)
    block = min(cfg["reference_block"], M)
    cast = partial_cast(dtype)
    init = cast(ref.init_state(kind, cfg, M, keys["init"]))
    init_host = host_copy(init)
    rdata = {k: ref.as_dtype(v, dtype) for k, v in data.items()}
    boot, _ = ref.run_rounds(kind, cfg, hp, {"kind": "full"}, init, rdata,
                             keys["boot"], 0, nb, None, sigma, block=block,
                             fault=fault)
    del init
    out = {"boot_change": compare.change_norms(boot, init_host),
           "dist": ref.l1_distances(boot["proxy"])}
    del init_host
    if groups is None:
        groups = ref.greedy_groups(out["dist"], p4["group_size"],
                                   p4["sample_peers"], seed)
    out["groups"] = groups
    boot_host = host_copy(boot)
    after, losses = ref.run_rounds(kind, cfg, hp, mix["schedule"], boot,
                                   rdata, keys["cotrain"], nb, nb + E, B,
                                   sigma, groups=groups, block=block,
                                   fault=fault)
    del boot
    out["change"] = compare.change_norms(after, boot_host)
    out["losses"] = losses
    out["correct"] = kind.correct_counts(cfg, after["private"],
                                         rdata["test_x"], rdata["test_y"])
    if keep:
        out["states"] = (boot_host, host_copy(after))
    return out


def host_copy(tree):
    """``tree`` copied to host memory: a copy, never a view of a device
    buffer (on the CPU backend ``device_get`` may return one), since the
    donated rounds that follow overwrite the device buffers in place."""
    return jax.tree_util.tree_map(lambda t: np.array(t, copy=True), tree)


def partial_cast(dtype):
    def cast(tree):
        return jax.tree_util.tree_map(lambda t: t.astype(dtype), tree)
    return cast
