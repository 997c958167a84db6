"""The ``jax.compile`` probe: where compiles happen, counted as they happen.

Registered as a ``jax.monitoring`` listener when ``repro.obs`` is imported.
It counts traces to a jaxpr (JAX reports a hit in its trace cache as a trace
too, so a ``jit.trace`` followed by the call counts two), lowerings to a
module, backend compiles (count and seconds; a load from the persistent
cache runs inside the same step and counts as one) and persistent-cache hits
and misses, with the last ``fun_name`` of each kind. ``Telemetry.chunk_span``
reports each chunk's compile count and seconds from the probe's delta, so a
run log shows which chunk recompiled.
"""
from __future__ import annotations

import jax

from repro.obs.probes import Probe

COMPILE_STATS = Probe("jax.compile", {
    "traces": 0, "trace_s": 0.0, "last_trace": "",
    "lowerings": 0, "lowering_s": 0.0, "last_lowering": "",
    "compiles": 0, "compile_s": 0.0, "last_compile": "",
    "cache_hits": 0, "last_cache_hit": "",
    "cache_misses": 0, "last_cache_miss": "",
})

_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": ("traces", "trace_s",
                                               "last_trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": (
        "lowerings", "lowering_s", "last_lowering"),
    "/jax/core/compile/backend_compile_duration": ("compiles", "compile_s",
                                                   "last_compile"),
}
_CACHE = {"/jax/compilation_cache/cache_hits": ("cache_hits",
                                                "last_cache_hit"),
          "/jax/compilation_cache/cache_misses": ("cache_misses",
                                                  "last_cache_miss")}
# the persistent-cache events carry no function name: they fire inside the
# backend-compile step, whose start (a scalar event) names the function
_COMPILING = [""]


def _on_duration(event, duration, fun_name="", **_):
    keys = _DURATIONS.get(event)
    if keys is not None:
        n, s, last = keys
        COMPILE_STATS[n] += 1
        COMPILE_STATS[s] += float(duration)
        COMPILE_STATS[last] = str(fun_name)


def _on_scalar(event, value, fun_name="", **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILING[0] = str(fun_name)


def _on_event(event, **_):
    keys = _CACHE.get(event)
    if keys is not None:
        n, last = keys
        COMPILE_STATS[n] += 1
        COMPILE_STATS[last] = _COMPILING[0]


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_scalar_listener(_on_scalar)
jax.monitoring.register_event_listener(_on_event)
