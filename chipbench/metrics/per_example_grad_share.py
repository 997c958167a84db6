"""Device time under the per-example gradients of the proxy's DP step and
their flatten to the (c, D) stack (``per_example_grads``), the layout copies
that follow them included; over the device's busy time in the traced slice
(``_layers``)."""
from chipbench.metrics import _layers


def read(ctx):
    return _layers.share(ctx, "per_example_grads")
