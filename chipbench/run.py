#!/usr/bin/env python3
"""Run one benchmark cell once on the chip it is started on.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (data and weights from the seed, P4's bootstrap, Phase-1 grouping,
one warm-up co-train chunk) counts as ``setup_s``; then co-train chunks run
for ``--seconds``, each followed by the personalized models' evaluation.
``--trace 1`` measures the same window, traces two of its chunks with the
JAX profiler and reports the per-layer metrics instead of the end-to-end
ones. After the window the plain reference (``chipbench/reference.py``)
follows the run from the seed and decides ``correct``.

The last line of stdout is the result as one JSON object. Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints none.
JAX's compile cache is kept in ``<checkout>/.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` names another.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        cell = harness.load_cell(args.workload)
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          t0=T0, log=log)
    except harness.NoChip as e:
        log(f"chipbench: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
