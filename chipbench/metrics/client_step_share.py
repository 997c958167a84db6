"""Device time under the strategy's local update in the round body
(``local_update``): both gradients, the metrics step and the ``dp_clip``
kernels are inside it; over the device's busy time in the traced slice
(``_layers``)."""
from chipbench.metrics import _layers


def read(ctx):
    return _layers.share(ctx, "local_update")
