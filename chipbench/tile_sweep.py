#!/usr/bin/env python3
"""Sweep the tiles of ``dp_clip`` and ``l1_distance`` at a cell's own shapes
on the chip, to pin them in the configuration file (the autotuner is off in
every cell).

    python3 chipbench/tile_sweep.py --workload <name>

``dp_clip`` is timed as the engine calls it: vmapped over the cell's
clients, on a (c, D) stack per client (c the per-example chunk, D the
parameters of one model). ``l1_distance`` on the (M, D) matrix of Phase 1.
Each candidate prints its median of five timed calls after a warm-up call,
timed around ``block_until_ready``. ``--write`` pins the fastest of each
kernel in the cell's configuration file.
"""
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _median_s(fn, x, n=5):
    import jax
    jax.block_until_ready(fn(x))
    ts = []
    for _ in range(n):
        t = time.perf_counter()
        jax.block_until_ready(fn(x))
        ts.append(time.perf_counter() - t)
    return sorted(ts)[n // 2]


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    from chipbench import harness
    from repro.kernels.dp_clip import ops as dp_ops
    from repro.kernels.l1_distance import ops as l1_ops
    if jax.devices()[0].platform != "tpu":
        print("tile_sweep: no TPU", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    cfg, M = cell["cfg"], cell["mix"]["clients"]
    c, D = cfg["dp"]["per_example_chunk"], cfg["params_per_model"]
    best = {}
    x = jax.random.normal(jax.random.PRNGKey(0), (M, c, D), jnp.float32)
    for tb in (8, 16):
        for td in (2048, 8192, 16384):
            f = jax.jit(jax.vmap(lambda a: dp_ops.clip_accumulate_flat(
                a, 1.0, denom=192.0, interpret=False, tb=tb, td=td)))
            t = _median_s(f, x)
            print(f"dp_clip ({M}, {c}, {D}) tile ({tb}, {td}): {t!r} s",
                  flush=True)
            best["dp_clip_tile"] = min(best.get("dp_clip_tile", (t, tb, td)),
                                       (t, tb, td))
    del x
    w = jax.random.normal(jax.random.PRNGKey(1), (M, D), jnp.float32)
    for tm in (8, 16):
        for td in (2048, 8192):
            f = jax.jit(lambda a: l1_ops.pairwise_l1(a, interpret=False,
                                                     tm=tm, td=td))
            t = _median_s(f, w, 3)
            print(f"l1_distance ({M}, {D}) tile ({tm}, {td}): {t!r} s",
                  flush=True)
            best["l1_tile"] = min(best.get("l1_tile", (t, tm, td)),
                                  (t, tm, td))
    print("fastest:", {k: list(v[1:]) for k, v in best.items()}, flush=True)
    if args.write:
        path = os.path.join(ROOT, "chipbench", "configs",
                            cell["config"] + ".json")
        with open(path) as fh:
            text = fh.read()
        for k, (_, a, b) in best.items():
            text = re.sub(rf'"{k}": \[\d+, \d+\]', f'"{k}": [{a}, {b}]', text)
        with open(path, "w") as fh:
            fh.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
