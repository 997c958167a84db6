"""Device time under the final lr = 0 client step that computes the round's
losses (``metrics_step``): its forwards, since XLA drops its unused
gradients; over the device's busy time in the traced slice (``_layers``)."""
from chipbench.metrics import _layers


def read(ctx):
    return _layers.share(ctx, "metrics_step")
