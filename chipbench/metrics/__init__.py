"""Per-layer metric readers: ``metrics/<name>.py`` holds ``read(ctx)``, which
returns the metric's value or None when the run has nothing to read for it
(the harness then leaves the metric out of the line). Unit, layer and the
end-to-end metric it moves are declared once, in ``BENCHMARK.json``."""
