"""The paper's linear model: one dense layer + softmax on feature rows."""
import jax.numpy as jnp

from chipbench.models import _classifier
from chipbench.models._classifier import (  # noqa: F401
    make_data, mutual_loss, run_correct)


def trainer_kwargs(cfg):
    return {"feat_dim": cfg["feat_dim"], "num_classes": cfg["num_classes"]}


def param_shapes(cfg):
    return {"w": (cfg["feat_dim"], cfg["num_classes"]),
            "b": (cfg["num_classes"],)}


def init_model(cfg, key):
    return _classifier.init_params(param_shapes(cfg), key)


def apply(cfg, params, x, prec):
    """Logits of one model on a batch x (B, F)."""
    return jnp.dot(x, params["w"], precision=prec) + params["b"]


def correct_counts(cfg, private, test_x, test_y):
    return _classifier.correct_counts(apply, cfg, private, test_x, test_y)


def shrink(cfg, mix):
    cfg["feat_dim"] = 64
