"""The numbers that decide ``correct``: the run under test against the plain
reference, each number held to its limit from ``limits/<cell>.json``.

* ``boot_change_gap``: the parameters' change over the bootstrap's rounds
  (the first steps; the engine's compiled chunk exposes state only at its
  end), by the worst leaf: |‖Δ_run‖ − ‖Δ_ref‖| over the larger of ‖Δ_ref‖
  of that leaf and of the median leaf. Leaves whose reference change is
  under a thousandth of the median leaf's are left out.
* ``dist_gap``: Phase 1's distances (Eq. 3), the run's against the
  reference's, largest gap over the larger of the reference's distance
  and its median (0 for a federation of one client, which has no pair).
* ``group_gap``: Phase 1's groups. The clients whose group differs between
  the run's grouping and the one the paper's greedy procedure forms on the
  run's own distances (0 when the two are the same partition).
* ``loss_gap``: each co-train round of the first chunk, the mean private
  and proxy losses, largest relative gap.
* ``change_gap``: the change over the first co-train chunk, as
  ``boot_change_gap``.
* ``eval_gap``: after that chunk, the number of test predictions of the
  personalized models that the run and the reference count differently.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _leaf_norm(x, y):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                       - y.astype(jnp.float32))))


def change_norms(new, old):
    """{"private/w": ‖new − old‖, ...} over every client's rows, one leaf at
    a time on the device: either tree may live on the host (a run's
    before-states do), and only one leaf of it is copied over at a time."""
    return {f"{model}/{k}": float(_leaf_norm(new[model][k], old[model][k]))
            for model in ("private", "proxy") for k in sorted(new[model])}


def norm_gap(run, ref) -> float:
    med = float(np.median(list(ref.values())))
    keep = [k for k in ref if ref[k] >= 1e-3 * med]
    return max(abs(run[k] - ref[k]) / max(ref[k], med) for k in keep)


def loss_gap(run, ref) -> float:
    run, ref = np.asarray(run, np.float64), np.asarray(ref, np.float64)
    if run.shape != ref.shape or not np.all(np.isfinite(run)):
        return math.inf
    return float(np.max(np.abs(run - ref) / np.maximum(np.abs(ref), 1e-30)))


def group_gap(run_groups, ref_groups) -> float:
    """Clients whose group (as a set of clients) differs between the two
    partitions."""
    def by_client(groups):
        return {i: frozenset(g) for g in groups for i in g}
    a, b = by_client(run_groups), by_client(ref_groups)
    return float(sum(a.get(i) != b.get(i) for i in set(a) | set(b)))


def dist_gap(run, ref) -> float:
    run, ref = np.asarray(run, np.float64), np.asarray(ref, np.float64)
    off = ~np.eye(ref.shape[0], dtype=bool)
    if run.shape != ref.shape or not np.all(np.isfinite(run[off])):
        return math.inf
    if not off.any():           # one client: no pair to compare
        return 0.0
    med = float(np.median(ref[off]))
    return float(np.max(np.abs(run[off] - ref[off])
                        / np.maximum(ref[off], med)))


def numbers(run, ref):
    """run, ref: dicts with boot_change, dist (M, M), groups, change,
    losses (rounds, 2) and correct (M,) per-client counts of right test
    predictions."""
    return {
        "boot_change_gap": norm_gap(run["boot_change"], ref["boot_change"]),
        "dist_gap": dist_gap(run["dist"], ref["dist"]),
        "group_gap": group_gap(run["groups"], ref["groups"]),
        "loss_gap": loss_gap(run["losses"], ref["losses"]),
        "change_gap": norm_gap(run["change"], ref["change"]),
        "eval_gap": float(np.sum(np.abs(np.asarray(run["correct"], np.int64)
                                        - np.asarray(ref["correct"],
                                                     np.int64)))),
    }


def diffs(run_states, ref_states, ref):
    """Diagnostics for setting limits, not compared in a run: by the worst
    leaf, the norm of the difference between the run's and the reference's
    change (over the bootstrap; over the first chunk), over the norm of the
    reference's change. Both sides start the bootstrap from the same
    weights, so the bootstrap's difference of changes is that of states."""
    boot = change_norms(run_states[0], ref_states[0])
    chunk = change_norms(
        jax.tree_util.tree_map(jnp.subtract, run_states[1], run_states[0]),
        jax.tree_util.tree_map(jnp.subtract, ref_states[1], ref_states[0]))
    return {"boot_change_diff": max(boot[k] / max(ref["boot_change"][k],
                                                  1e-30) for k in boot),
            "change_diff": max(chunk[k] / max(ref["change"][k], 1e-30)
                               for k in chunk)}


def verdict(nums, limits):
    """(correct, checks): every number that has a limit, with it."""
    checks = {k: {"value": nums[k], "limit": limits[k]}
              for k in sorted(limits) if k in nums}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok and len(checks) == len(limits), checks
