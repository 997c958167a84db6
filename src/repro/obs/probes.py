"""Unified counter probes: one registry over every trace-time stats dict.

The repo grew one module-global counter dict per subsystem — ``CHUNK_STATS``
(compiled-chunk cache), ``MIX_STATS`` (mixing paths/collectives), the
autotuner's hit/miss tallies, the cohort prefetcher's staleness counters —
each with its own ad-hoc reset function and each forcing callers who want
*per-run* numbers into hand-rolled ``before = dict(STATS)`` arithmetic
(and silently inflated numbers when they forget: nothing resets between
Engine instances in one process).

``Probe`` is a ``dict`` subclass, so the existing module globals keep their
exact semantics — ``CHUNK_STATS["hits"] += 1``, ``dict(CHUNK_STATS)``,
``.update(...)`` all behave identically and every pre-existing test passes
unedited — while registration gives every counter group a shared API:

  * ``registry.snapshot()`` — point-in-time copy of every probe;
  * ``registry.reset()``    — zero everything (template-typed zeros);
  * ``probe_deltas(...)``   — a scoped context manager measuring exactly
    what happened inside the ``with`` block, replacing the hand-diffed
    snapshot arithmetic. Scopes nest and compose: each scope owns its own
    entry snapshot, so an inner scope's counts are a subset of the outer's.

This module is deliberately stdlib-only: probe-owning modules (e.g.
``topology.mixing``) import it at module load, before jax is necessarily
initialized.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Iterable, Optional, Tuple


class Probe(dict):
    """A named group of counters. Plain-dict reads/writes remain the hot-path
    increment idiom (``PROBE["hits"] += 1`` at trace time costs one dict op);
    the registry layers snapshot/reset/delta semantics on top."""

    def __init__(self, name: str, counters: Dict[str, float],
                 registry: "Optional[ProbeRegistry]" = None):
        super().__init__(counters)
        self.name = name
        # typed zero template: ``reset`` restores these values; keys added
        # after construction reset to int 0
        self._zeros = dict(counters)
        (registry if registry is not None else REGISTRY).register(self)

    def snapshot(self) -> Dict[str, float]:
        return dict(self)

    def reset(self) -> None:
        for k in self:
            self[k] = self._zeros.get(k, 0)

    def delta_from(self, before: Dict[str, float]) -> Dict[str, float]:
        """Counter movement since ``before`` (a prior ``snapshot()``). Keys
        born after the snapshot count from their typed zero. A value that is
        not a number (a last-seen name) reads as it is now."""
        return {k: (v - before.get(k, self._zeros.get(k, 0))
                    if isinstance(v, (int, float)) else v)
                for k, v in self.items()}

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return f"Probe({self.name!r}, {dict(self)!r})"


class ProbeRegistry:
    """Process-global name → Probe index. Registration happens at module
    import of the probe's owner, so ``snapshot()`` covers exactly the
    subsystems the process has loaded."""

    def __init__(self):
        self._probes: "Dict[str, Probe]" = {}
        self._lock = threading.Lock()

    def register(self, probe: Probe) -> Probe:
        with self._lock:
            self._probes[probe.name] = probe
        return probe

    def get(self, name: str) -> Probe:
        try:
            return self._probes[name]
        except KeyError:
            raise KeyError(
                f"no probe named {name!r} is registered (loaded probes: "
                f"{sorted(self._probes)})") from None

    def names(self) -> Tuple[str, ...]:
        return tuple(sorted(self._probes))

    def _select(self, names: Optional[Iterable[str]]) -> Tuple[Probe, ...]:
        if names is None:
            return tuple(self._probes[n] for n in self.names())
        return tuple(self.get(n) for n in names)

    def snapshot(self, names: Optional[Iterable[str]] = None
                 ) -> Dict[str, Dict[str, float]]:
        return {p.name: p.snapshot() for p in self._select(names)}

    def reset(self, names: Optional[Iterable[str]] = None) -> None:
        for p in self._select(names):
            p.reset()

    @contextlib.contextmanager
    def deltas(self, *names: str):
        """Scoped measurement: yields a ``ProbeDeltas`` whose per-probe
        counter movements cover exactly the ``with`` block. With no names,
        every probe registered at scope entry is measured."""
        sel = self._select(names or None)
        d = ProbeDeltas({p.name: p.snapshot() for p in sel}, self)
        try:
            yield d
        finally:
            d.finalize()


class ProbeDeltas:
    """The result object of a ``deltas`` scope. Inside the scope,
    ``d[name]`` reads the movement so far (live); after the scope it is
    frozen at the block's exit values. Mapping-style access only covers the
    probes the scope selected."""

    def __init__(self, before: Dict[str, Dict[str, float]],
                 registry: ProbeRegistry):
        self._before = before
        self._registry = registry
        self._frozen: Optional[Dict[str, Dict[str, float]]] = None

    def finalize(self) -> None:
        if self._frozen is None:
            self._frozen = {n: self._registry.get(n).delta_from(b)
                            for n, b in self._before.items()}

    def __getitem__(self, name: str) -> Dict[str, float]:
        if self._frozen is not None:
            return dict(self._frozen[name])
        if name not in self._before:
            raise KeyError(f"probe {name!r} was not selected by this scope")
        return self._registry.get(name).delta_from(self._before[name])

    def get(self, name: str, default=None):
        try:
            return self[name]
        except KeyError:
            return default

    def asdict(self) -> Dict[str, Dict[str, float]]:
        return {n: self[n] for n in self._before}

    def keys(self):
        return self._before.keys()


#: The process-global registry every subsystem probe registers with.
REGISTRY = ProbeRegistry()


def get_probe(name: str) -> Probe:
    return REGISTRY.get(name)


def probe_snapshot(names: Optional[Iterable[str]] = None):
    return REGISTRY.snapshot(names)


def reset_probes(names: Optional[Iterable[str]] = None) -> None:
    REGISTRY.reset(names)


def probe_deltas(*names: str):
    """Module-level alias for ``REGISTRY.deltas`` — the scoped-delta API:

        with probe_deltas("engine.chunk_cache") as d:
            engine.fit(...)
        print(d["engine.chunk_cache"])   # {"traces": 1, "hits": 3, ...}
    """
    return REGISTRY.deltas(*names)
