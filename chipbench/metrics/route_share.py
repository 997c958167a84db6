"""Device time under the expert share's router, top-k, sort, gather and scatter
(``moe_route``); over the device's busy time in the traced slice
(``_layers``)."""
from chipbench.metrics import _layers


def read(ctx):
    return _layers.share(ctx, "moe_route")
