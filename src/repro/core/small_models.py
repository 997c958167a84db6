"""The paper's evaluation models (§4.1): a linear classifier (one layer +
softmax) and the Tramèr–Boneh CNN [47], both consuming either ScatterNet
features or raw images."""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.models.module import ParamSpec, init_params


def linear_specs(feat_dim: int, num_classes: int):
    return {
        "w": ParamSpec((feat_dim, num_classes), ("embed", "vocab"), init="fan_in"),
        "b": ParamSpec((num_classes,), ("vocab",), init="zeros"),
    }


def linear_apply(params, x):
    """x: (B, feat) -> logits (B, classes)."""
    return jnp.einsum("bf,fc->bc", x, params["w"].astype(jnp.float32)) + params["b"]


def cnn_specs(in_ch: int, num_classes: int, width: int = 32):
    """Small CNN (conv-relu-pool ×2 + linear), applied to (B, C, H, W)."""
    return {
        "c1": ParamSpec((width, in_ch, 3, 3), (None, None, None, None), init="fan_in"),
        "c2": ParamSpec((2 * width, width, 3, 3), (None, None, None, None), init="fan_in"),
        "w": ParamSpec((0, num_classes), ("embed", "vocab"), init="fan_in"),  # resolved lazily
        "b": ParamSpec((num_classes,), ("vocab",), init="zeros"),
    }


class LayerSeam(NamedTuple):
    """What a per-layer DP route needs of a model beside its ``apply``.

    ``forward(params, x, taps)`` is ``apply`` with ``taps[name]`` added to
    layer ``name``'s output before its activation; it returns the logits and
    ``inputs``, each parametric layer's input by name. Differentiated with
    respect to ``zero_taps(n)`` for a batch of n, it gives every example's
    loss gradient at every layer's output from one batched backward, with no
    per-example ``jax.grad``. ``kinds`` names each layer's form: ``"conv3x3"``
    (3x3 SAME, stride 1, no bias, NCHW) or ``"dense"`` (x·w + b)."""
    forward: Callable
    zero_taps: Callable
    kinds: Dict[str, str]


def make_cnn(in_shape: Tuple[int, int, int], num_classes: int, width: int = 32):
    """Returns (specs, apply) with the linear head sized for ``in_shape``
    (C, H, W). ``apply.layer_seam`` is the model's ``LayerSeam``."""
    C, H, W = in_shape
    h2, w2 = H // 4 or 1, W // 4 or 1
    feat = 2 * width * h2 * w2
    specs = {
        "c1": ParamSpec((width, C, 3, 3), (None, None, None, None), init="fan_in"),
        "c2": ParamSpec((2 * width, width, 3, 3), (None, None, None, None), init="fan_in"),
        "w": ParamSpec((feat, num_classes), ("embed", "vocab"), init="fan_in"),
        "b": ParamSpec((num_classes,), ("vocab",), init="zeros"),
    }

    def forward(params, x, taps):
        """x: (B, C, H, W) [or (B, C*H*W) flattened] -> (logits, inputs);
        ``taps`` None adds nothing."""
        if x.ndim == 2:
            x = x.reshape(x.shape[0], C, H, W)
        def conv(t, k):
            return jax.lax.conv_general_dilated(
                t, k, (1, 1), "SAME", dimension_numbers=("NCHW", "OIHW", "NCHW"))
        def tap(t, name):
            return t if taps is None else t + taps[name]
        inputs = {"c1": x}
        x = jax.nn.relu(tap(conv(x, params["c1"].astype(jnp.float32)), "c1"))
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 2, 2), (1, 1, 2, 2), "VALID")
        inputs["c2"] = x
        x = jax.nn.relu(tap(conv(x, params["c2"].astype(jnp.float32)), "c2"))
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 2, 2), (1, 1, 2, 2), "VALID")
        x = x.reshape(x.shape[0], -1)
        inputs["head"] = x
        logits = jnp.einsum("bf,fc->bc", x, params["w"].astype(jnp.float32)) + params["b"]
        return tap(logits, "head"), inputs

    def zero_taps(n):
        return {"c1": jnp.zeros((n, width, H, W), jnp.float32),
                "c2": jnp.zeros((n, 2 * width, H // 2, W // 2), jnp.float32),
                "head": jnp.zeros((n, num_classes), jnp.float32)}

    def apply(params, x):
        """x: (B, C, H, W) [or (B, C*H*W) flattened] -> logits."""
        return forward(params, x, None)[0]

    apply.layer_seam = LayerSeam(forward, zero_taps,
                                 {"c1": "conv3x3", "c2": "conv3x3",
                                  "head": "dense"})
    return specs, apply


def accuracy(logits, labels):
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
