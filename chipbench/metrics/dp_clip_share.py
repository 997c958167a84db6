"""Device time of the ``dp_clip`` kernels over the device's busy time in the
traced slice."""
from chipbench.metrics import _dp_clip


def read(ctx):
    t = ctx.trace
    if t is None or t["busy_s"] <= 0:
        return None
    s = _dp_clip.seconds(t)
    return None if s <= 0 else 100.0 * s / t["busy_s"]
