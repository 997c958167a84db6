"""``dp_clip``'s share of its roofline: the least time the chip needs for
the calls in the traced slice (bytes the algorithm needs over peak HBM
bandwidth, or operations over peak FLOP/s, whichever is larger;
counts/dp_clip.py) over the kernels' summed device time. Each launch covers
every client of the vmapped step with a (c, D) stack, c the configuration's
per-example chunk and D the model's parameter count."""
from chipbench.counts import dp_clip
from chipbench.metrics import _dp_clip


def read(ctx):
    t = ctx.trace
    if t is None or ctx.peaks is None:
        return None
    secs, n = _dp_clip.seconds(t), _dp_clip.launches(t)
    if secs <= 0 or n <= 0:
        return None
    cfg = ctx.cfg
    c = cfg["dp"]["per_example_chunk"] or ctx.mix["local_batch"]
    D = cfg["params_per_model"]
    calls = n * ctx.mix["clients"]
    least = max(calls * dp_clip.call_bytes(c, D) / ctx.peaks["hbm_bytes_per_s"],
                calls * dp_clip.call_flops(c, D) / ctx.peaks["flops_per_s"])
    return 100.0 * least / secs
