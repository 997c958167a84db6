"""Device time under the language model's attention layers (their norms,
projections, RoPE and blocked attention, sliding and full), forward and
backward (``attention``); over the device's busy time in the traced slice
(``_layers``)."""
from chipbench.metrics import _layers


def read(ctx):
    return _layers.share(ctx, "attention")
