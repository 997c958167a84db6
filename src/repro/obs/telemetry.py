"""Run-level telemetry: phase spans, a JSONL event log, a run manifest, an
opt-in in-jit metrics tap, and opt-in profiler capture.

A ``Telemetry`` object owns one run directory:

  * ``events.jsonl``  — append-only event stream (spans, per-round tap
    records, eval points, profiler captures); one JSON object per line so
    ``launch/monitor.py`` can tail a live run;
  * ``manifest.json`` — config/strategy/topology fingerprints, the mesh,
    per-phase records, the cumulative (ε, δ)/accuracy trajectory copied
    from ``History`` at every eval boundary, and a closing probe snapshot.

The engine integrates through narrow seams (``Engine._chunk_span`` /
``_dispatch_chunk`` / the eval loop in ``fit``), all of which check
``telemetry is None or not telemetry.enabled`` FIRST — a run without
telemetry builds byte-identical chunk-cache keys and traces byte-identical
chunks (locked by the telemetry-off equivalence scenario); its program
spans (``repro.obs.spans``) reach the profiler only.

The tap (``tap=True``) restructures the chunk's scan into blocks of
``TAP_BLOCK`` rounds (identical per-round ops and outputs) with one
``io_callback`` per block streaming per-round scalars (loss/grad-norm-style
metrics means, participation count, realized σ, fault up/slow/keep) to the
event log while the chunk is still executing. Because the callbacks are
part of the traced computation, tap on/off participates in the chunk-cache
fingerprint — a tapped chunk is never served to an untapped engine or vice
versa. The
sharded engine keeps its shard_map trace tap-free and streams the same
per-round events host-side from the chunk's stacked metric outputs instead
(same schema, emitted at chunk completion).

Profiler capture: ``profile_chunk=N`` wraps the Nth dispatched chunk in
``jax.profiler.trace`` (Perfetto trace under ``<run_dir>/profile``).
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, Optional

import numpy as np

from repro.obs.probes import REGISTRY

# ---------------------------------------------------------------------------
# Active-telemetry routing: the io_callback target is this module-level
# dispatcher, NOT a bound method — cached tapped chunks stay reusable across
# Telemetry instances because the sink is resolved per execution. The slot is
# a process-wide global (NOT thread-local): XLA delivers host callbacks on
# its own worker thread, so a thread-local set on the dispatching thread
# would be invisible to the sink.
# ---------------------------------------------------------------------------

_ACTIVE: "Optional[Telemetry]" = None


def current_telemetry() -> "Optional[Telemetry]":
    return _ACTIVE


# The tap's field names never cross the device boundary: at trace time the
# ordered key tuple is interned here and only a small integer schema id
# rides the callback (one flat f32 vector instead of a dict pytree — each
# extra operand costs a host transfer per round). Ids are process-lifetime,
# like the chunk cache, so cached tapped chunks resolve their schema on
# every later execution.
_TAP_SCHEMAS: Dict[int, tuple] = {}
_TAP_SCHEMA_IDS: Dict[tuple, int] = {}


def _schema_id(keys: tuple) -> int:
    sid = _TAP_SCHEMA_IDS.get(keys)
    if sid is None:
        sid = len(_TAP_SCHEMAS)
        _TAP_SCHEMA_IDS[keys] = sid
        _TAP_SCHEMAS[sid] = keys
    return sid


def _tap_sink(sid, r0, table) -> None:
    """Execution-time sink — deliberately minimal (runs on XLA's callback
    thread): append the raw block, format at drain time."""
    tel = current_telemetry()
    if tel is None:
        return
    tel._tap_append(int(sid), int(r0), np.asarray(table, np.float32))


# Rounds per streamed block. A per-round io_callback stalls the scanned
# round pipeline (~0.3–0.5 ms per call on CPU — measured in bench_obs, and
# most of it is XLA host-callback dispatch, not the Python sink), so the
# tap scans in blocks of TAP_BLOCK rounds and streams one (block, fields)
# table per block: the per-round tax drops by ~TAP_BLOCK× while every
# round still lands in the event log.
TAP_BLOCK = 32


def tap_scan(body, state, rs, rt):
    """Tapped twin of ``lax.scan(body, state, rs)``: identical per-round
    ops and identical stacked outputs (the tap-on ≡ tap-off bit-exactness
    contract), but scanned in blocks of ``TAP_BLOCK`` rounds with one
    unordered io_callback per block streaming the block's per-round
    scalars. Only traced when the engine's tap is on, so the tap-off trace
    contains no callback (and no nested scan) at all. The engine's
    ``jax.effects_barrier()`` inside the activation window guarantees
    every callback lands before the chunk span closes."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import io_callback

    length = int(rs.shape[0])
    K = min(TAP_BLOCK, length)

    def emit(r0, n, metrics, aux):
        keys, cols = [], []

        def flat(v):
            return v.reshape(v.shape[0], -1).astype(jnp.float32)

        for k, v in (metrics or {}).items():
            keys.append(k)
            cols.append(jnp.mean(flat(v), axis=1))
        for k, v in (aux or {}).items():
            if k == "participation":
                keys.append(k)
                cols.append(jnp.sum(flat(v), axis=1))
            elif k.startswith("fault_"):
                keys.append(k)
                cols.append(jnp.mean(flat(v), axis=1))
        if rt and "sigma" in rt:
            keys.append("sigma")
            cols.append(jnp.broadcast_to(
                jnp.asarray(rt["sigma"], jnp.float32), (n,)))
        sid = _schema_id(tuple(keys))
        table = (jnp.stack(cols, axis=1) if cols
                 else jnp.zeros((n, 0), jnp.float32))
        io_callback(_tap_sink, None, jnp.int32(sid),
                    jnp.asarray(r0, jnp.int32), table, ordered=False)

    def block(state, rs_block):
        state, ys = jax.lax.scan(body, state, rs_block)
        metrics, aux = ys
        emit(rs_block[0], int(rs_block.shape[0]), metrics, aux)
        return state, ys

    nblocks, rem = divmod(length, K)
    state, ys = jax.lax.scan(block, state,
                             rs[:nblocks * K].reshape(nblocks, K))
    ys = jax.tree_util.tree_map(
        lambda a: a.reshape((nblocks * K,) + a.shape[2:]), ys)
    if rem:
        state, ys_tail = block(state, rs[nblocks * K:])
        ys = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], axis=0), ys, ys_tail)
    return state, ys


class Telemetry:
    """One training run's observability sink. ``enabled=False`` is the
    provably-free off switch: the engine treats it exactly like
    ``telemetry=None`` (no spans, no tap, no files, unchanged cache keys)."""

    def __init__(self, run_dir: Optional[str] = None, *, tap: bool = False,
                 enabled: bool = True, profile_chunk: Optional[int] = None):
        self.enabled = bool(enabled) and run_dir is not None
        self.run_dir = run_dir
        self.tap = bool(tap)
        self.profile_chunk = profile_chunk
        self._lock = threading.Lock()
        self._events_f = None
        self._tap_pending: list = []
        self._chunk_idx = 0
        self._manifest: Dict[str, Any] = {"phases": [], "trajectory": []}
        self._manifest_dirty = False
        self._manifest_written = False
        if self.enabled:
            os.makedirs(run_dir, exist_ok=True)

    # ------------------------------------------------------------- low level
    @property
    def events_path(self) -> str:
        return os.path.join(self.run_dir, "events.jsonl")

    @property
    def manifest_path(self) -> str:
        return os.path.join(self.run_dir, "manifest.json")

    def _emit(self, ev: Dict[str, Any]) -> None:
        if not self.enabled:
            return
        ev.setdefault("t", time.time())
        with self._lock:
            self._drain_tap_locked()
            if self._events_f is None:
                self._events_f = open(self.events_path, "a")
            self._events_f.write(json.dumps(ev) + "\n")
            self._events_f.flush()

    # ------------------------------------------------------- tap hot path
    def _tap_append(self, sid: int, start: int, table,
                    source: Optional[str] = None) -> None:
        """Tap hot path (the io_callback sink and the sharded post-chunk
        stream land here): buffer one raw (rounds, fields) block; JSON
        formatting and file I/O happen once per flush boundary, not once
        per round."""
        with self._lock:
            self._tap_pending.append((time.time(), sid, start, table,
                                      source))

    def _drain_tap_locked(self) -> None:
        if not self._tap_pending:
            return
        pending, self._tap_pending = self._tap_pending, []
        if self._events_f is None:
            self._events_f = open(self.events_path, "a")
        lines = []
        for t, sid, start, table, source in pending:
            keys = _TAP_SCHEMAS.get(sid, ())
            tail = (f', "source": {json.dumps(source)}, "t": {t!r}}}'
                    if source is not None else f', "t": {t!r}}}')
            if keys and bool(np.isfinite(table).all()):
                # fast path: keys are plain metric names and every value is
                # finite, so hand-built lines are valid JSON — per-row
                # json.dumps is ~5x slower and this runs once per round
                for i, row in enumerate(table.tolist()):
                    mid = "".join(f', "{k}": {v!r}'
                                  for k, v in zip(keys, row))
                    lines.append(
                        f'{{"type": "tap", "round": {start + i}{mid}{tail}')
            elif keys:
                for i, row in enumerate(table):
                    ev: Dict[str, Any] = {"type": "tap", "round": start + i}
                    ev.update(zip(keys, (float(x) for x in row)))
                    if source is not None:
                        ev["source"] = source
                    ev["t"] = t
                    lines.append(json.dumps(ev))
            else:
                lines.extend(
                    f'{{"type": "tap", "round": {start + i}{tail}'
                    for i in range(table.shape[0]))
        self._events_f.write("\n".join(lines) + "\n")
        self._events_f.flush()

    def flush(self) -> None:
        if self._manifest_dirty:
            self._write_manifest()
        with self._lock:
            self._drain_tap_locked()
            if self._events_f is not None:
                self._events_f.flush()

    def _write_manifest(self) -> None:
        with self._lock:
            tmp = self.manifest_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self._manifest, f, indent=1, default=str)
            os.replace(tmp, self.manifest_path)
            self._manifest_dirty = False
            self._manifest_written = True

    def close(self) -> None:
        if self._manifest_dirty:
            self._write_manifest()
        with self._lock:
            self._drain_tap_locked()
            if self._events_f is not None:
                self._events_f.close()
                self._events_f = None

    # ----------------------------------------------------------------- spans
    @contextlib.contextmanager
    def activate(self):
        """Execution-time routing context for the in-jit tap's io_callbacks
        (installed by the engine around chunk dispatch; the engine blocks on
        the chunk inside this context, so the callbacks land before exit)."""
        global _ACTIVE
        prev = _ACTIVE
        _ACTIVE = self
        try:
            yield
        finally:
            _ACTIVE = prev

    def span(self, name: str, **fields):
        """A program span (``repro.obs.spans.span``) that writes
        {"type": "span", "name", "id", "parent", "chunk", "dt", ...} here
        and shows on the profiler's timeline under the same id."""
        from repro.obs.spans import span
        return span(name, self, **fields)

    @contextlib.contextmanager
    def chunk_span(self, **fields):
        """The ``engine.run_rounds`` span of one chunk, with the
        trace-vs-execute split and cache hits/misses read off the
        chunk-cache probe (a cache hit executes without tracing), the
        chunk's compile count and seconds off the ``jax.compile`` probe, the
        DP routes its trace took off the ``dp.path`` probe, and its mixing
        path off the mix probe. The profiler capture of the Nth
        chunk rides this span."""
        from repro.obs.spans import span
        if not self.enabled:
            with span("engine.run_rounds", chunk=True):
                yield
            return
        idx = self._chunk_idx
        self._chunk_idx += 1
        sel = [n for n in ("engine.chunk_cache", "topology.mix",
                           "jax.compile", "dp.path") if n in REGISTRY.names()]
        profiled = (self.profile_chunk is not None
                    and idx == self.profile_chunk)
        prof_dir = os.path.join(self.run_dir, "profile")
        prof_cm = contextlib.nullcontext()
        if profiled:
            try:
                import jax
                prof_cm = jax.profiler.trace(prof_dir)
            except Exception:  # profiler unavailable on this backend
                profiled = False
        with prof_cm:
            sp = span("engine.run_rounds", self, chunk=True, **fields)
            with sp, REGISTRY.deltas(*sel) as d:
                try:
                    yield
                finally:
                    d.finalize()
                    sp.fields.update(self._chunk_fields(d, profiled,
                                                        prof_dir))

    @staticmethod
    def _chunk_fields(d, profiled: bool, prof_dir: str) -> Dict[str, Any]:
        cache = d.get("engine.chunk_cache") or {}
        ev: Dict[str, Any] = {
            "traced": bool(cache.get("traces", 0) > 0),
            "cache": {k: int(cache.get(k, 0))
                      for k in ("traces", "hits", "misses")}}
        comp = d.get("jax.compile") or {}
        ev["compiles"] = int(comp.get("compiles", 0))
        ev["compile_s"] = float(comp.get("compile_s", 0.0))
        if ev["compiles"]:
            ev["compiled"] = comp.get("last_compile", "")
        routes = {k: int(v) for k, v in (d.get("dp.path") or {}).items() if v}
        if routes:
            ev["dp_path"] = routes
        mix = d.get("topology.mix") or {}
        if mix.get("calls", 0) > 0:
            paths = {k[len("path_"):]: v for k, v in mix.items()
                     if k.startswith("path_") and v > 0}
            ev["mix_path"] = max(paths, key=paths.get) if paths else None
            ev["collectives"] = {"all_gathers": int(mix.get("all_gathers", 0)),
                                 "ppermutes": int(mix.get("ppermutes", 0))}
        if profiled:
            ev["profile_dir"] = prof_dir
        return ev

    # ------------------------------------------------------------ run record
    def begin_phase(self, info: Dict[str, Any]) -> None:
        """Called by ``Engine.fit`` at phase start with the run's identity:
        strategy/schedule/topology fingerprints, mesh, rounds, batch size.
        The first phase writes the manifest eagerly so a live monitor can
        identify the run; later phases only mark it dirty (the atomic
        rewrite is ~0.5 ms of syscalls — real per-fit money in a sweep of
        short phases) and land at the next ``flush``/``close``."""
        if not self.enabled:
            return
        info = dict(info, t=time.time())
        self._manifest["phases"].append(info)
        self._manifest.setdefault("created", time.time())
        if self._manifest_written:
            self._manifest_dirty = True
        else:
            self._write_manifest()
        self._emit(dict({"type": "phase_begin"}, **info))

    def eval_event(self, round_: int, accuracy: float,
                   metrics: Dict[str, float]) -> None:
        """One eval-boundary record, copied from the History entry AFTER it
        is recorded — the JSONL trajectory and the returned History agree
        exactly by construction."""
        if not self.enabled:
            return
        ev = {"type": "eval", "round": int(round_),
              "accuracy": float(accuracy)}
        ev.update({k: float(v) for k, v in metrics.items()})
        self._emit(ev)
        self._manifest["trajectory"].append(
            {k: v for k, v in ev.items() if k not in ("type", "t")})

    def end_phase(self) -> None:
        """Phase close: records the probe snapshot and marks the manifest
        dirty. The on-disk rewrite (an atomic replace, ~0.5 ms of syscalls)
        is deferred to the next ``begin_phase``/``flush``/``close`` — the
        event log is the crash-safe record, so the manifest is allowed to
        run one phase stale while a run is live."""
        if not self.enabled:
            return
        self._manifest["probes"] = REGISTRY.snapshot()
        self._manifest_dirty = True
        self._emit({"type": "phase_end"})

    # ------------------------------------------- sharded (post-chunk) stream
    def emit_tap_stacked(self, start: int, length: int, metrics, aux,
                         rt) -> None:
        """Host-side twin of the in-jit tap for engines whose chunk trace
        must stay tap-free (shard_map regions): emits the same per-round
        event schema from the chunk's stacked metric outputs. Reductions
        are vectorized over the round axis and the per-round records take
        the same buffered drain path as the io_callback sink. ``length``
        is the chunk's round count — the stream covers every round even
        for strategies that surface no per-round metrics."""
        if not (self.enabled and self.tap):
            return
        keys, cols = [], []
        for k, v in (metrics or {}).items():
            a = np.asarray(v, np.float32)
            if a.ndim == 0:
                continue
            keys.append(k)
            cols.append(a.reshape(a.shape[0], -1).mean(axis=1))
        for k, v in (aux or {}).items():
            if k != "participation" and not k.startswith("fault_"):
                continue
            a = np.asarray(v, np.float32)
            if a.ndim == 0:
                continue
            flat = a.reshape(a.shape[0], -1)
            keys.append(k)
            cols.append(flat.sum(axis=1) if k == "participation"
                        else flat.mean(axis=1))
        length = int(length)
        if not length:
            return
        cols = [c[:length] for c in cols]
        if rt and "sigma" in rt:
            keys.append("sigma")
            cols.append(np.full((length,), float(np.asarray(rt["sigma"])),
                                np.float32))
        table = (np.stack(cols, axis=1) if cols
                 else np.zeros((length, 0), np.float32))
        self._tap_append(_schema_id(tuple(keys)), int(start), table,
                         source="chunk")
