"""Model FLOPs of the window's co-train rounds (6 forward units per example
and local step, counts/__init__.py) over the window's seconds and the chip's
peak FLOP/s from peaks.json."""


def read(ctx):
    if ctx.peaks is None or ctx.window["seconds"] <= 0:
        return None
    flops = ctx.window["samples"] * ctx.step_flops_per_example
    return 100.0 * flops / ctx.window["seconds"] / (
        ctx.peaks["flops_per_s"] * ctx.chips)
