"""BENCHMARK.json against the benchmark's contract, and the harness finding
every configuration, traffic mix, limit file and metric reader by name."""
import json
import os
import re
import shutil

import pytest

from chipbench import counts, harness
from chipbench_util import cell_names

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert bench["command"][1].startswith(bench["paths"][0] + "/")
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_bounds(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_configs_are_used_and_match_their_files(bench):
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used and NAME.match(c["name"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert counts.model(cfg).forward_macs(cfg) > 0


@pytest.mark.parametrize("cell_name", cell_names())
def test_every_cell_resolves(bench, cell_name):
    cell = harness.load_cell(cell_name)
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    assert cell["limits"] and cell["per_layer"] and len(cell["end_to_end"]) >= 2
    for m in cell["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_cell_added_by_files_alone(bench, tmp_path):
    """A new traffic mix, cell, limit file and metric reader in a copy of
    the benchmark are found with no other file edited."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench")
    b = dict(bench)
    b["workloads"] = bench["workloads"] + [{
        "name": "linear-c10.k5", "config": "p4-linear-cifar10",
        "traffic": "full-m256-k5", "chips": 1, "why": "K = 5 local steps"}]
    b["per_layer"] = bench["per_layer"] + [{
        "name": "rounds_in_window", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "engine round loop",
        "moves": "train_samples_per_s", "workloads": ["linear-c10.k5"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    mix = json.loads((tmp_path / "chipbench/traffic/full-m256.json").read_text())
    mix["local_steps"] = 5
    (tmp_path / "chipbench/traffic/full-m256-k5.json").write_text(json.dumps(mix))
    shutil.copy(tmp_path / "chipbench/limits/linear-c10.full.json",
                tmp_path / "chipbench/limits/linear-c10.k5.json")
    (tmp_path / "chipbench/metrics/rounds_in_window.py").write_text(
        "def read(ctx):\n    return float(ctx.window['rounds'])\n")
    cell = harness.load_cell("linear-c10.k5", root=str(tmp_path))
    assert cell["mix"]["local_steps"] == 5
    assert "rounds_in_window" in [m["name"] for m in cell["per_layer"]]
    read = harness.reader("rounds_in_window", root=str(tmp_path))
    assert read(harness.Context(window={"rounds": 30}, setup={}, trace=None,
                                peaks=None, chips=1, step_flops_per_example=1,
                                cfg={}, mix={})) == 30.0
    assert harness.participants(cell["mix"]) == 256.0
