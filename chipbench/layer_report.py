#!/usr/bin/env python3
"""Device time of one cell by layer of the round step, from a traced run.

    python3 chipbench/layer_report.py --workload <name> --seed <n> --seconds <s>

Makes the ``--trace 1`` run of ``run.py`` and prints two JSON lines: its
result line, then the breakdown of the traced slice's busy time by layer
(``metrics/_layers.breakdown``): seconds under each layer, under no layer
and not in the op -> layer table, with the largest ops the table does not
know. Without a TPU it exits non-zero.
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
    args = ap.parse_args()

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness
    from chipbench.metrics import _layers

    seen = {}
    reader = harness.reader

    def capture(name, root=harness.ROOT):
        read = reader(name, root)

        def read_and_keep(ctx):
            seen["ctx"] = ctx
            return read(ctx)
        return read_and_keep

    harness.reader = capture
    try:
        cell = harness.load_cell(args.workload)
        out = harness.run(cell, args.seed, args.seconds, True, t0=T0,
                          log=lambda m: print(m, file=sys.stderr, flush=True))
    except harness.NoChip as e:
        print(f"layer_report: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    ctx = seen.get("ctx")
    if ctx is None or ctx.trace is None:
        print("layer_report: the run has no device trace", file=sys.stderr)
        return 1
    print(json.dumps(_layers.breakdown(ctx.trace, _layers.op_table())),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
