"""Device time under the held experts' grouped products, forward and backward
(``moe_experts``); over the device's busy time in the traced slice
(``_layers``)."""
from chipbench.metrics import _layers


def read(ctx):
    return _layers.share(ctx, "moe_experts")
