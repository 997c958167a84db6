"""Program spans: host time on the profiler's own timeline.

A span is a ``jax.profiler.TraceAnnotation`` carrying a span id, its
parent's id and the chunk index, so a profile shows it on the same clock as
the device ops it dispatched. When a ``Telemetry`` is enabled, the same span
(same id, parent and ``dt``) is also written to its ``events.jsonl``.

The spans the program opens, at chunk and phase granularity only:

    engine.run_rounds        one per chunk (``chunk`` index assigned here)
      engine.chunk_lookup    chunk-cache key and lookup (or build)
      engine.dispatch        the call of the compiled chunk
    p4.evaluate              the personalized models' evaluation
    p4.form_groups           Phase-1 grouping
      p4.l1_distance         pairwise distances, up to their reaching the host
      p4.greedy              the greedy procedure on the host

A span's telemetry is the one passed in, else its parent span's, else the
engine's active one (``telemetry.current_telemetry``). With no profiler
running and no telemetry, a span is one inactive TraceMe and nothing else:
no id is drawn and nothing is kept.

The names ``window``, ``dispatch``, ``eval`` and ``sync`` are the
benchmark harness's own annotations; no program span may take them.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, Optional

from jax.profiler import TraceAnnotation

from repro.obs.telemetry import current_telemetry

RESERVED = ("window", "dispatch", "eval", "sync")

_IDS = itertools.count(1)
_LOCAL = threading.local()
_CHUNK = [-1]        # index of the last recorded chunk span


def _stack():
    st = getattr(_LOCAL, "stack", None)
    if st is None:
        st = _LOCAL.stack = []
    return st


class span:
    """``with span("engine.dispatch"): ...`` — see the module docstring.
    ``chunk=True`` opens a new chunk (the engine's per-chunk span); every
    other span carries the index of the chunk it runs in or after.
    ``fields`` (and whatever the block adds to ``.fields``) go to the
    telemetry event only."""

    __slots__ = ("name", "telemetry", "fields", "new_chunk", "id", "parent",
                 "chunk", "dt", "_tm", "_t0", "_recorded")

    def __init__(self, name: str, telemetry=None, *, chunk: bool = False,
                 **fields: Any):
        if name in RESERVED:
            raise ValueError(f"span name {name!r} is reserved for the "
                             "benchmark harness's annotations")
        self.name = name
        self.telemetry = telemetry
        self.new_chunk = chunk
        self.fields: Dict[str, Any] = fields
        self.id: Optional[int] = None
        self.parent: Optional[int] = None
        self.chunk: Optional[int] = None
        self.dt: Optional[float] = None
        self._recorded = False

    def _resolve_telemetry(self):
        tel = self.telemetry
        if tel is None:
            st = _stack()
            if st:
                tel = st[-1].telemetry
            else:
                tel = current_telemetry()
        return tel if (tel is not None and tel.enabled) else None

    def __enter__(self) -> "span":
        tel = self._resolve_telemetry()
        if tel is None and not TraceAnnotation.is_enabled():
            self._tm = TraceAnnotation(self.name)
            self._tm.__enter__()
            return self
        self._recorded = True
        self.telemetry = tel
        st = _stack()
        self.id = next(_IDS)
        self.parent = st[-1].id if st else 0
        if self.new_chunk:
            _CHUNK[0] += 1
        self.chunk = _CHUNK[0]
        st.append(self)
        self._tm = TraceAnnotation(self.name, id=self.id, parent=self.parent,
                                   chunk=self.chunk)
        self._tm.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if not self._recorded:
            self._tm.__exit__(*exc)
            return
        self.dt = time.perf_counter() - self._t0
        self._tm.__exit__(*exc)
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        if self.telemetry is not None:
            self.telemetry._emit(dict(
                {"type": "span", "name": self.name, "id": self.id,
                 "parent": self.parent, "chunk": self.chunk, "dt": self.dt},
                **self.fields))
