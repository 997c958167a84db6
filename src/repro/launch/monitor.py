"""Tail / summarize a telemetry run directory (``repro.obs.Telemetry``).

    PYTHONPATH=src python -m repro.launch.monitor results/run1          # summary
    PYTHONPATH=src python -m repro.launch.monitor results/run1 --follow # live tail
    PYTHONPATH=src python -m repro.launch.monitor results/run1 --tail 20

The summary reads ``manifest.json`` + ``events.jsonl`` and reports the run's
identity (phases, fingerprints, mesh), the (ε, δ)/accuracy trajectory, span
aggregates with the trace-vs-execute split (chunks that hit the compiled-
chunk cache vs chunks that traced), tap-stream coverage, and the closing
probe snapshot. ``--follow`` tails the event stream, rendering one line per
event as it lands — usable against a live run from another process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, Iterator, List, Optional

#: the engine's per-chunk span (``repro.obs.spans``): its events carry the
#: trace-vs-execute split, cache counts and the chunk's compiles
CHUNK_SPAN = "engine.run_rounds"


def load_manifest(run_dir: str) -> Optional[Dict[str, Any]]:
    path = os.path.join(run_dir, "manifest.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def load_events(run_dir: str) -> List[Dict[str, Any]]:
    path = os.path.join(run_dir, "events.jsonl")
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    pass  # a line mid-write during a live tail
    return out


def _fmt_event(ev: Dict[str, Any]) -> str:
    t = ev.get("type", "?")
    if t == "span":
        extra = ""
        if ev.get("name") == CHUNK_SPAN:
            extra = (f" chunk={ev.get('chunk')} rounds=[{ev.get('start')},"
                     f"{ev.get('stop')}) "
                     f"{'traced' if ev.get('traced') else 'cached'}")
            if ev.get("compiles"):
                extra += (f" compiles={ev['compiles']} "
                          f"({ev.get('compile_s', 0.0):.3f}s, "
                          f"{ev.get('compiled', '')})")
            if ev.get("mix_path"):
                extra += f" mix={ev['mix_path']}"
            if "profile_dir" in ev:
                extra += " [profiled]"
        return f"span {ev.get('name'):<20} {ev.get('dt', 0):8.4f}s{extra}"
    if t == "tap":
        vals = {k: v for k, v in ev.items()
                if k not in ("type", "t", "round", "source")}
        body = " ".join(f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                        for k, v in sorted(vals.items()))
        return f"tap  round={ev.get('round'):<6} {body}"
    if t == "eval":
        vals = {k: v for k, v in ev.items() if k not in ("type", "t")}
        body = " ".join(f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                        for k, v in sorted(vals.items()))
        return f"eval {body}"
    return f"{t} " + json.dumps({k: v for k, v in ev.items()
                                 if k not in ("type", "t")}, default=str)


def summarize(run_dir: str) -> str:
    manifest = load_manifest(run_dir)
    events = load_events(run_dir)
    lines = [f"run: {run_dir}"]
    if manifest:
        for i, ph in enumerate(manifest.get("phases", [])):
            lines.append(
                f"phase {i}: {ph.get('engine')}/{ph.get('strategy')} "
                f"{ph.get('schedule')} rounds=[{ph.get('start_round')},"
                f"{ph.get('rounds')}) batch={ph.get('batch_size')} "
                f"mesh={ph.get('mesh')}")
        traj = manifest.get("trajectory", [])
        if traj:
            last = traj[-1]
            eps = last.get("dp_epsilon")
            lines.append(
                f"trajectory: {len(traj)} evals, last round="
                f"{last.get('round')} acc={last.get('accuracy'):.4f}"
                + (f" eps={eps:.4g} delta={last.get('dp_delta'):.3g}"
                   if eps is not None else ""))

    spans: Dict[str, List[Dict[str, Any]]] = {}
    taps = 0
    tap_rounds: List[int] = []
    for ev in events:
        if ev.get("type") == "span":
            spans.setdefault(ev.get("name", "?"), []).append(ev)
        elif ev.get("type") == "tap":
            taps += 1
            tap_rounds.append(int(ev.get("round", -1)))
    for name in sorted(spans):
        evs = spans[name]
        total = sum(e.get("dt", 0.0) for e in evs)
        if name == CHUNK_SPAN:
            traced = [e for e in evs if e.get("traced")]
            cached = [e for e in evs if not e.get("traced")]

            def agg(sub):
                return (f"{len(sub)}x mean "
                        f"{(sum(e.get('dt', 0.0) for e in sub) / len(sub)):.4f}s"
                        if sub else "0x")

            lines.append(f"span {name}: {len(evs)}x total {total:.3f}s — "
                         f"traced(+compile) {agg(traced)}, "
                         f"execute-only {agg(cached)}")
            recompiled = [e for e in evs if e.get("compiles")]
            if recompiled:
                lines.append("  compiled in: " + ", ".join(
                    f"chunk {e.get('chunk')} ({e['compiles']}x, "
                    f"{e.get('compile_s', 0.0):.3f}s)" for e in recompiled))
            paths = sorted({e.get("mix_path") for e in evs
                            if e.get("mix_path")})
            if paths:
                lines.append(f"  mix paths: {', '.join(paths)}")
            prof = [e for e in evs if "profile_dir" in e]
            if prof:
                lines.append(f"  profiler capture: chunk "
                             f"{prof[0].get('chunk')} → "
                             f"{prof[0]['profile_dir']}")
        else:
            lines.append(f"span {name}: {len(evs)}x total {total:.3f}s")
    if taps:
        lines.append(f"tap: {taps} rounds streamed "
                     f"[{min(tap_rounds)}..{max(tap_rounds)}]")
    if manifest and manifest.get("probes"):
        for pname, counters in sorted(manifest["probes"].items()):
            nz = {k: v for k, v in counters.items() if v}
            lines.append(f"probe {pname}: {nz or dict(counters)}")
    if len(lines) == 1:
        lines.append("(no telemetry found — is this a Telemetry run_dir?)")
    return "\n".join(lines)


def follow(run_dir: str, poll: float = 0.5) -> Iterator[str]:
    """Yield one formatted line per event as the stream grows (tail -f)."""
    path = os.path.join(run_dir, "events.jsonl")
    pos = 0
    while True:
        if os.path.exists(path):
            with open(path) as f:
                f.seek(pos)
                for line in f:
                    if not line.endswith("\n"):
                        break  # partial write: re-read next poll
                    pos += len(line)
                    line = line.strip()
                    if line:
                        try:
                            yield _fmt_event(json.loads(line))
                        except json.JSONDecodeError:
                            pass
        time.sleep(poll)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Tail/summarize a repro.obs.Telemetry run directory")
    ap.add_argument("run_dir")
    ap.add_argument("--follow", action="store_true",
                    help="tail the event stream live")
    ap.add_argument("--tail", type=int, default=0, metavar="N",
                    help="print the last N events and exit")
    args = ap.parse_args(argv)
    if args.follow:
        try:
            for line in follow(args.run_dir):
                print(line, flush=True)
        except KeyboardInterrupt:
            return 0
    elif args.tail:
        for ev in load_events(args.run_dir)[-args.tail:]:
            print(_fmt_event(ev))
    else:
        print(summarize(args.run_dir))
    return 0


if __name__ == "__main__":
    sys.exit(main())
