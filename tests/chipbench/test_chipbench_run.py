"""The harness end to end at a size a CPU test run holds: the entry refuses
a machine without the chip, and an in-process rehearsal drives a whole run
(set-up, window, reference, verdict) and prints the contract's last line."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench_util import ROOT, cell_names, shrink


def test_entry_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "linear-c10.full", "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.NoChip):
        harness.peaks_for("TPU v99")
    assert harness.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12


@pytest.mark.parametrize("cell_name", cell_names())
def test_rehearsal_prints_the_result_line(cell_name, capsys):
    cell = shrink(harness.load_cell(cell_name))
    out = harness.run(cell, 2 ** 33 + 5, 0.2, False, t0=0.0,
                      require_chip=False, kernels={"backend": "ref"},
                      log=lambda m: None)
    line = json.loads(json.dumps(out))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "peak_hbm_gib",
                                    "setup_s"}
    assert set(line["checks"]) == set(cell["limits"])
    assert line["device"]["platform"] == "cpu"


def test_hbm_peak_adds_the_reserved_program_memory():
    """Live buffers and the temporaries reserved for loaded executables are
    counted apart by the TPU allocator; the peak holds both."""
    stats = {"peak_bytes_in_use": 4_477_854_720,
             "peak_bytes_reserved": 9_888_940_032, "bytes_in_use": 1}
    assert harness.hbm_peak(stats) == 14_366_794_752
    assert harness.hbm_peak({"peak_bytes_in_use": 7}) == 7


def test_seed_keys_take_wide_seeds():
    a, b = harness.seed_key(2 ** 31 + 7), harness.seed_key(7)
    assert a.shape == (2,) and not (a == b).all()
    assert (harness.seed_key(2 ** 40 + 1) != harness.seed_key(1)).any()


def test_traced_rehearsal_reports_per_layer_metrics():
    """On the CPU the trace holds no TPU plane and there is no peak, so the
    device readers return nothing and leave their metrics out."""
    cell = shrink(harness.load_cell("linear-c10.full"))
    out = harness.run(cell, 5, 0.2, True, t0=0.0, require_chip=False,
                      kernels={"backend": "ref"}, log=lambda m: None)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"compiles_in_window", "grouping_s",
                                   "compile_s"}
    assert out["metrics"]["compiles_in_window"]["value"] == 0.0
    assert "busy_s" not in out["device"] and "breakdown" not in out


def test_sampled_schedule_is_followed_by_the_reference():
    """A traffic file alone makes the sampled cell: the full cell's mix with
    a fixed cohort of a quarter of the clients, which the reference draws
    alike (stream 3), masking the group means alike."""
    cell = harness.load_cell("linear-c10.sampled-q25")
    full = harness.load_cell("linear-c10.full")["mix"]
    assert {k: v for k, v in cell["mix"].items()
            if k not in ("schedule", "why")} == {
        k: v for k, v in full.items() if k not in ("schedule", "why")}
    assert harness.participants(cell["mix"]) == 64.0
    cell = shrink(cell)
    out = harness.run(cell, 9, 0.1, False, t0=0.0, require_chip=False,
                      kernels={"backend": "ref"}, log=lambda m: None)
    assert out["correct"] is True
    assert harness.participants(cell["mix"]) == 4.0
