"""The comparison that decides ``correct`` fails where it must: the
lower-precision control (the reference in bfloat16, put in the program's
place) and the faults a training cell can have, planted underneath a whole
run that skips only the harness's look for a chip."""
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import calibrate, compare, harness
from repro.engine.loop import clear_chunk_cache
from chipbench_util import cell_names, shrink


def _run(cell, plant):
    """A run with ``plant`` breaking the program; the compiled chunks are
    cleared on both sides, since the cache key does not see a patched
    method and unbroken and broken programs would share them."""
    def planted(p):
        clear_chunk_cache()
        plant(p)
    try:
        return harness.run(cell, 11, 0.1, False, t0=0.0, require_chip=False,
                           kernels={"backend": "ref"}, plant=planted,
                           log=lambda m: None)
    finally:
        clear_chunk_cache()


def _unchanged(p):
    zeros = {"private_loss": jnp.zeros(()), "proxy_loss": jnp.zeros(())}
    p.strategy.local_update = lambda states, xs, ys, r, key: (states, zeros)
    _no_exchange(p)


def _half_batch(p):
    step = p.trainer._local_round_impl
    p.trainer._local_round_impl = lambda states, xs, ys, key: step(
        states, xs[:, : xs.shape[1] // 2], ys[:, : ys.shape[1] // 2], key)


def _no_exchange(p):
    """No aggregation: over every client, or over a sampled cohort."""
    p.strategy.aggregate = lambda states, r, key: states
    p.strategy.aggregate_masked = lambda states, r, key, mask: states


def _swapped_groups(p):
    """Phase 1 answers wrongly: the first client of the first two groups
    trade places."""
    form = p.trainer.form_groups

    def swapped(states, seed=0, topology=None):
        groups = [list(g) for g in form(states, seed)]
        groups[0][0], groups[1][0] = groups[1][0], groups[0][0]
        return [sorted(g) for g in groups]
    p.trainer.form_groups = swapped


CELLS = cell_names()


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _no_exchange,
                                   _swapped_groups],
                         ids=["state_unchanged", "half_batch", "no_exchange",
                              "swapped_groups"])
def test_planted_fault_reads_not_correct(cell_name, fault):
    out = _run(shrink(harness.load_cell(cell_name)), fault)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_unchanged_state_reads_one():
    out = _run(shrink(harness.load_cell("linear-c10.full")), _unchanged)
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_swapped_groups_read_on_group_gap():
    out = _run(shrink(harness.load_cell("linear-c10.full")), _swapped_groups)
    assert out["checks"]["group_gap"]["value"] > 0


def test_phase_one_numbers():
    a = [[0, 1, 2], [3, 4, 5], [6, 7]]
    assert compare.group_gap(a, [[7, 6], [5, 4, 3], [2, 1, 0]]) == 0.0
    assert compare.group_gap(a, [[0, 1, 3], [2, 4, 5], [6, 7]]) == 6.0
    d = np.array([[0.0, 2.0, 4.0], [2.0, 0.0, 1.0], [4.0, 1.0, 0.0]])
    assert compare.dist_gap(d, d) == 0.0
    e = d.copy()
    e[1, 2] = e[2, 1] = 1.5          # 0.5 off, over the median 2
    assert compare.dist_gap(e, d) == pytest.approx(0.25)
    e[0, 1] = np.nan
    assert compare.dist_gap(e, d) == float("inf")


@pytest.mark.parametrize("cell_name", CELLS)
def test_lower_precision_control_fails(cell_name):
    cell = shrink(harness.load_cell(cell_name))
    rows = calibrate.calibrate(cell, [], [4], emit=lambda line: None)
    control = next(r for r in rows if r["kind"] == "control")
    ok, _ = compare.verdict(control, cell["limits"])
    assert not ok


def test_limits_lie_between_the_readings():
    def row(kind, gap, groups, ev):
        return {"kind": kind, "boot_change_gap": gap, "dist_gap": gap,
                "group_gap": groups, "loss_gap": gap, "change_gap": gap,
                "eval_gap": ev}
    rows = [row("program", 1e-5, 0.0, 0.0), row("program", 2e-5, 0.0, 2.0),
            row("control", 2e-3, 64.0, 40.0),
            row("half_batch", 0.9, 30.0, 500.0)]
    limits, readings = calibrate.limits_from(rows)
    assert readings["loss_gap"] == {"program_max": 2e-5, "control_min": 2e-3,
                                    "half_batch_min": 0.9}
    assert limits["group_gap"] == 0.0
    for k in ("boot_change_gap", "dist_gap", "loss_gap", "change_gap",
              "eval_gap"):
        lower = readings[k]["program_max"]
        assert 3 * lower < limits[k] < readings[k]["control_min"] / 2
    # the control too close: the half-batch fault or an unchanged state
    # (1 on the change gaps) sets the upper reading instead
    rows[2] = row("control", 3e-5, 0.0, 3.0)
    limits, _ = calibrate.limits_from(rows)
    assert 6e-5 < limits["loss_gap"] < 0.9 and limits["change_gap"] < 1.0
    assert "group_gap" in limits and limits["eval_gap"] > 3.0
    rows[0]["group_gap"] = 8.0
    assert "group_gap" not in calibrate.limits_from(rows)[0]
