"""Backend compiles (jax.monitoring) plus engine chunk traces
(``engine.chunk_cache`` probe) that happened inside the measured window."""


def read(ctx):
    return float(ctx.window["compiles"])
