"""The repo's CNN on the ScatterNet stack: 3x3 SAME conv (width, no bias)
-> relu -> 2x2 max pool -> 3x3 SAME conv (2 width) -> relu -> 2x2 max pool
-> linear head, on feature rows laid out as ``cnn_shape`` (C, H, W)."""
import jax
import jax.numpy as jnp

from chipbench.models import _classifier
from chipbench.models._classifier import (  # noqa: F401
    make_data, mutual_loss, run_correct)


def trainer_kwargs(cfg):
    return {"feat_dim": cfg["feat_dim"], "num_classes": cfg["num_classes"],
            "model": "cnn", "cnn_shape": tuple(cfg["cnn_shape"])}


def param_shapes(cfg):
    ch, h, w = cfg["cnn_shape"]
    width = cfg["cnn_width"]
    feat = 2 * width * max(h // 4, 1) * max(w // 4, 1)
    return {"c1": (width, ch, 3, 3), "c2": (2 * width, width, 3, 3),
            "w": (feat, cfg["num_classes"]), "b": (cfg["num_classes"],)}


def init_model(cfg, key):
    return _classifier.init_params(param_shapes(cfg), key)


def apply(cfg, params, x, prec):
    """Logits of one model on a batch x (B, F)."""
    ch, h, w = cfg["cnn_shape"]
    t = x.reshape(x.shape[0], ch, h, w)

    def conv(t, k):
        return jax.lax.conv_general_dilated(
            t, k, (1, 1), "SAME", dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=prec)

    def pool(t):
        return jax.lax.reduce_window(t, -jnp.inf, jax.lax.max,
                                     (1, 1, 2, 2), (1, 1, 2, 2), "VALID")
    t = pool(jax.nn.relu(conv(t, params["c1"])))
    t = pool(jax.nn.relu(conv(t, params["c2"])))
    t = t.reshape(t.shape[0], -1)
    return jnp.dot(t, params["w"], precision=prec) + params["b"]


def correct_counts(cfg, private, test_x, test_y):
    return _classifier.correct_counts(apply, cfg, private, test_x, test_y)


def shrink(cfg, mix):
    cfg["feat_dim"] = 64
    cfg["cnn_shape"] = [4, 4, 4]
