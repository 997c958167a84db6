"""Observability: unified counter probes (``jax.compile`` among them), named
layers of the round step with their op -> layer table, program spans on the
profiler's timeline, a JSONL event stream, and an opt-in in-jit metrics tap
— with a zero-overhead-off guarantee (telemetry absent or disabled changes
nothing: no files, unchanged chunk-cache keys, bit-identical traces; a span
is one inactive TraceMe and a layer scope is trace metadata)."""
from repro.obs.probes import (Probe, ProbeRegistry, REGISTRY, get_probe,
                              probe_deltas, probe_snapshot, reset_probes)
from repro.obs.compiles import COMPILE_STATS
from repro.obs.layers import LAYERS, hlo_layers, layer, op_layers
from repro.obs.spans import span
from repro.obs.telemetry import (Telemetry, current_telemetry, tap_scan)

__all__ = [
    "Probe", "ProbeRegistry", "REGISTRY", "get_probe", "probe_deltas",
    "probe_snapshot", "reset_probes", "COMPILE_STATS", "LAYERS",
    "hlo_layers", "layer", "op_layers", "span", "Telemetry",
    "current_telemetry", "tap_scan",
]
