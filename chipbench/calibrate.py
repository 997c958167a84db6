#!/usr/bin/env python3
"""Readings from which the limits of ``limits/<cell>.json`` are set.

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,... \\
        [--controls 1,2,3] [--out <file.jsonl>] [--write]

For every seed of ``--seeds`` the program runs from the seed through its
bootstrap, Phase 1 and the first co-train chunk (as a benchmark run's
set-up does) and the plain reference follows it: the program's numbers.
For every seed of ``--controls`` the reference itself is then put in the
program's place twice, and the float32 reference follows each:

* ``control``: the whole reference in bfloat16, the precision below the
  configuration's float32;
* ``half_batch``: the float32 reference with a planted fault, each step
  using half of its batch and the mean over that half.

(The fault "a step returns its state unchanged" reads 1 on both change
gaps by construction and needs no run.) Each line printed is one JSON
object: seed, kind and the numbers of ``compare.numbers``. ``--write``
sets the cell's limits from these readings (``limits_from``) and writes
them with the readings to ``limits/<cell>.json``. The benchmark's own runs
never run this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seeds(s):
    return [int(x) for x in s.split(",") if x]


def calibrate(cell, seeds, controls, *, kernels=None, emit=print):
    """Yields (seed, kind, numbers) for the program on ``seeds`` and for the
    control and the half-batch fault on ``controls``."""
    import jax.numpy as jnp
    from chipbench import harness
    cfg, mix = cell["cfg"], cell["mix"]
    out = []
    for seed in sorted(set(seeds) | set(controls)):
        keys = harness.run_keys(seed)
        data = cell["kind"].make_data(mix, cfg, keys["data"], seed)
        rows = []
        if seed in seeds:
            _, _, state, run_out, _, _ = harness.first_chunk(
                cell, seed, keys, data, kernels=kernels, keep=True)
            del state
            t = time.perf_counter()
            nums = harness.follow_reference(cell, data, keys, run_out, seed)
            rows.append(("program", dict(nums, reference_s=time.perf_counter()
                                         - t)))
        if seed in controls:
            for kind, dtype, fault in (("control", jnp.bfloat16, None),
                                       ("half_batch", jnp.float32,
                                        "half_batch")):
                cand = harness.reference_outputs(
                    cell, data, keys, dtype=dtype, fault=fault, seed=seed,
                    keep=True)
                rows.append((kind, harness.follow_reference(
                    cell, data, keys, cand, seed)))
        for kind, nums in rows:
            rec = {"seed": seed, "kind": kind, **nums}
            emit(json.dumps(rec))
            out.append(rec)
        del data
    return out


NUMBERS = ("boot_change_gap", "dist_gap", "group_gap", "loss_gap",
           "change_gap", "eval_gap")


def limits_from(rows):
    """(limits, readings) from calibration rows. The lower reading of a
    number is the largest the program gives; its upper reading the smallest
    the control gives where that is three times the lower or more, or the
    half-batch fault's where that is ten times the lower or more, or, on
    the two change gaps, the 1 that a state left unchanged reads where that
    is three times the lower or more, whichever is smallest. The limit lies between them at lower·(upper/lower)^0.6, two
    significant digits: more room above the lower reading than below the
    upper. A count (``eval_gap``) takes a lower reading of at least 1. A
    number with no upper reading is not compared. ``group_gap`` is an exact
    comparison: limit 0, where every program reading is 0."""
    def by(kind, k):
        return [r[k] for r in rows if r["kind"] == kind]
    limits, readings = {}, {}
    for k in NUMBERS:
        lower = max(by("program", k))
        ctrl, half = min(by("control", k)), min(by("half_batch", k))
        readings[k] = {"program_max": lower, "control_min": ctrl,
                       "half_batch_min": half}
        if k == "group_gap":
            if lower == 0:
                limits[k] = 0.0
            continue
        if k == "eval_gap":
            lower = max(lower, 1.0)
        cands = [(ctrl, 3), (half, 10)]
        if k in ("boot_change_gap", "change_gap"):
            cands.append((1.0, 3))
        uppers = [u for u, times in cands if lower > 0 and u >= times * lower]
        if uppers:
            upper = min(uppers)
            limits[k] = float(f"{lower * (upper / lower) ** 0.6:.2g}")
    return limits, readings


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--controls", type=_seeds, default=[])
    ap.add_argument("--out")
    ap.add_argument("--write", action="store_true",
                    help="set the cell's limits from these readings")
    args = ap.parse_args()
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    f = open(args.out, "a") if args.out else None

    def emit(line):
        print(line, flush=True)
        if f:
            f.write(line + "\n")
            f.flush()
    rows = calibrate(cell, args.seeds, args.controls, emit=emit)
    if args.write:
        limits, readings = limits_from(rows)
        readings["source"] = (
            f"chipbench/calibrate.py on {jax.devices()[0].device_kind}: "
            f"program on seeds {args.seeds}, control and half-batch fault "
            f"on {args.controls}")
        path = os.path.join(ROOT, "chipbench", "limits",
                            args.workload + ".json")
        with open(path, "w") as fh:
            json.dump({"limits": limits, "readings": readings}, fh, indent=2)
            fh.write("\n")
        print("limits:", json.dumps(limits), flush=True)
    print(f"calibrate: {time.perf_counter() - T0:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
