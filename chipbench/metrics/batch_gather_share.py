"""Device time under the per-client minibatch draw and the (M, B, ...)
gather from the training stacks (``sample_batches``); over the device's busy
time in the traced slice (``_layers``)."""
from chipbench.metrics import _layers


def read(ctx):
    return _layers.share(ctx, "sample_batches")
