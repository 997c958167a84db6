"""Work of the paper's CNN on the ScatterNet stack: 3x3 SAME conv (width)
-> relu -> 2x2 max pool -> 3x3 SAME conv (2 width) -> relu -> 2x2 max pool
-> linear head.

A convolution counts the taps that touch the input: SAME padding's zeros
are not work the model needs (XLA's cost analysis counts the same way).
Counted with the padded taps, conv1 would be 4,478,976 MACs and conv2
294,912."""


def _taps(n: int, k: int = 3) -> int:
    """Kernel taps inside an n-wide input, summed over the n outputs of a
    k-wide SAME convolution along one axis."""
    r = k // 2
    return sum(min(i + r, n - 1) - max(i - r, 0) + 1 for i in range(n))


def layer_macs(cfg) -> dict:
    ch, h, w = cfg["cnn_shape"]
    width, C = cfg["cnn_width"], cfg["num_classes"]
    h2, w2 = h // 2, w // 2
    return {"conv1": width * ch * _taps(h) * _taps(w),
            "conv2": 2 * width * width * _taps(h2) * _taps(w2),
            "head": 2 * width * (h2 // 2) * (w2 // 2) * C}


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one example's forward pass."""
    return sum(layer_macs(cfg).values())
