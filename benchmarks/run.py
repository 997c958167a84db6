"""Benchmark harness — one module per paper table/figure (+ roofline/kernels).

  bench_heterogeneity  Figs. 2/3/5/6   accuracy vs heterogeneity, all methods
  bench_privacy        Fig. 7          accuracy vs ε, P4 vs local
  bench_ablation       Fig. 8          component ablation
  bench_overhead       §4.5            phase run time / bytes / memory
  bench_roofline       §Roofline       dry-run-derived terms per combo
  bench_kernels        (framework)     Pallas-vs-oracle microbench
  bench_engine         (framework)     scan round loop vs legacy Python loop
  bench_schedule       (framework)     round schedules vs the PR-2 loop
  bench_topology       (framework)     gossip loop vs graph family/density
  bench_population     (framework)     paged rounds/sec vs virtual M
  bench_resilience     (framework)     accuracy/overhead vs fault regime
  bench_obs            (framework)     telemetry overhead + off-is-free

Prints ``name,us_per_call,derived`` CSV. ``--full`` uses paper-scale rounds.
Suites exposing ``LAST_RECORDS`` also write ``BENCH_<suite>.json``, stamped
with the platform, device kind and device count they ran on. A failing
suite does not stop the others, but the run then exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# make `python benchmarks/run.py` work without PYTHONPATH incantations
for _p in (REPO_ROOT, os.path.join(REPO_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args()
    quick = not args.full

    from benchmarks import (bench_ablation, bench_engine, bench_heterogeneity,
                            bench_kernels, bench_obs, bench_overhead,
                            bench_population, bench_privacy, bench_resilience,
                            bench_roofline, bench_schedule, bench_topology)
    suites = {
        "kernels": bench_kernels,
        "engine": bench_engine,
        "schedule": bench_schedule,
        "topology": bench_topology,
        "population": bench_population,
        "resilience": bench_resilience,
        "overhead": bench_overhead,
        "roofline": bench_roofline,
        "privacy": bench_privacy,
        "ablation": bench_ablation,
        "heterogeneity": bench_heterogeneity,
        "obs": bench_obs,
    }
    rows, failed = [], []
    for name, mod in suites.items():
        if args.only and name not in args.only:
            continue
        t0 = time.time()
        print(f"\n===== {name} =====", flush=True)
        try:
            rows.extend(mod.run(quick=quick))
        except Exception as e:  # a failing suite must not hide the others
            print(f"[{name}] FAILED: {type(e).__name__}: {e}", file=sys.stderr)
            rows.append((f"{name}_FAILED", 0.0, type(e).__name__))
            failed.append(name)
        print(f"===== {name} done in {time.time()-t0:.0f}s =====", flush=True)
        if getattr(mod, "LAST_RECORDS", None):
            import jax
            dev = jax.devices()[0]
            payload = {"platform": dev.platform,
                       "device_kind": dev.device_kind,
                       "device_count": len(jax.devices()),
                       "quick": quick,
                       "entries": mod.LAST_RECORDS}
            out_path = os.path.join(REPO_ROOT, f"BENCH_{name}.json")
            with open(out_path, "w") as f:
                json.dump(payload, f, indent=2)
            print(f"[{name}] wrote {out_path}", flush=True)

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if failed:
        print(f"\n{len(failed)} suite(s) failed: {', '.join(failed)}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
