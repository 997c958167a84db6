"""Reduce a JAX profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes (or a gzipped copy),
through ``jax.profiler.ProfileData``. Device planes are ``/device:TPU:<n>``;
their ``XLA Ops`` line holds one event per operation run on the device,
named by its HLO text (``%sq_norms.7 = f32[...] custom-call(...)``), and a
control-flow op (a scanned ``while``) spans the ops it runs, which nest
inside it. The traced slice is the host annotation ``window_annotation``
that the harness writes around the traced chunks; everything is clipped to
it.

* busy: the union of the op intervals of each device, averaged over devices;
* op time by name (``sq_norms.7``): each op's self time, its span less the
  ops nested in it, summed over devices and divided by their number; so is
  the count of its events;
* idle gaps: each gap between busy intervals, labelled by the innermost
  host annotation of the harness (``dispatch``, ``eval``, ``sync``) that
  covers its midpoint, summed per label.
"""
from __future__ import annotations

import glob
import gzip
import os
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
HOST_LABELS = ("dispatch", "eval", "sync")
TOP = 10


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def reduce_dir(trace_dir: str, window_annotation: str = "window",
               labels: Sequence[str] = HOST_LABELS):
    path = find_xplane(trace_dir)
    return None if path is None else reduce_file(path, window_annotation,
                                                 labels)


def reduce_file(path: str, window_annotation: str = "window",
                labels: Sequence[str] = HOST_LABELS) -> Optional[Dict]:
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    return reduce_profile(pd, window_annotation, labels)


def _merge(intervals: List[Tuple[float, float]]):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """(name, self time) of each (start, end, name) event; an event nested
    in another is subtracted from it."""
    out, stack = [], []
    for s, e, name in sorted(events, key=lambda v: (v[0], -v[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        out.append([name, e - s])
        if stack:
            out[stack[-1][2]][1] -= e - s
        stack.append((s, e, len(out) - 1))
    return out


def reduce_profile(pd, window_annotation: str = "window",
                   labels: Sequence[str] = HOST_LABELS) -> Optional[Dict]:
    host: Dict[str, List[Tuple[float, float]]] = {}
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(e.start_ns, e.start_ns + e.duration_ns,
                                     e.name.split(" = ")[0].lstrip("%"))
                                    for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == window_annotation or e.name in labels:
                        host.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    if not devices or window_annotation not in host:
        return None
    w0 = min(s for s, _ in host[window_annotation])
    w1 = max(e for _, e in host[window_annotation])
    busy_ns, ops, calls, gaps = 0.0, {}, {}, {}
    for events in devices:
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in events
                   if e > w0 and s < w1]
        for n, t in _self_times(clipped):
            ops[n] = ops.get(n, 0.0) + t
            calls[n] = calls.get(n, 0) + 1
        merged = _merge([(s, e) for s, e, _ in clipped])
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                label = _label((a + b) / 2, host, labels)
                gaps[label] = gaps.get(label, 0.0) + (b - a)
    n = len(devices)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy_ns / n * 1e-9,
            "devices": n,
            "ops": {k: v / n * 1e-9 for k, v in ops.items()},
            "calls": {k: v / n for k, v in calls.items()},
            "top_ops": [[k, v / n * 1e-9] for k, v in top],
            "idle_gaps": [[k, v / n * 1e-9] for k, v in
                          sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]]}


def _label(t, host, labels):
    best, width = "other", float("inf")
    for name in labels:
        for s, e in host.get(name, ()):
            if s <= t <= e and e - s < width:
                best, width = name, e - s
    return best
