"""Device time under the group mean of the proxies with the participation
merges around it (``aggregate``); over the device's busy time in the traced
slice (``_layers``)."""
from chipbench.metrics import _layers


def read(ctx):
    return _layers.share(ctx, "aggregate")
