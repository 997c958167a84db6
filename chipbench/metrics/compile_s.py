"""Seconds XLA spent in set-up producing executables: backend compiles, or
persistent-cache loads when the entry is there."""


def read(ctx):
    return ctx.setup.get("compile_s")
