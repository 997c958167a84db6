"""Work counts against hand counts and against XLA's cost analysis of the
program's own forward pass at the configurations' full widths."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import counts, harness
from chipbench.counts import cnn, dp_clip


# the paper's CNN on the ScatterNet stack of CIFAR-10 (paper_cnn.py); its
# cell waits under PERF.md's open questions
CNN = {"model": "cnn", "feat_dim": 15552, "num_classes": 10,
       "cnn_shape": [243, 8, 8], "cnn_width": 32}


def _cfg(name):
    if name == "p4-cnn-cifar10":
        return dict(CNN)
    with open(os.path.join(harness.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_hand_counts():
    lin, conv = _cfg("p4-linear-cifar10"), _cfg("p4-cnn-cifar10")
    assert counts.model(lin).forward_macs(lin) == 155_520
    # 3x3 SAME taps inside the input: 22 per axis on 8 pixels, 10 on 4
    assert cnn.layer_macs(conv) == {"conv1": 32 * 243 * 22 * 22,
                                    "conv2": 64 * 32 * 10 * 10,
                                    "head": 2_560}
    assert counts.model(conv).forward_macs(conv) == 3_970_944
    assert counts.step_flops_per_example(lin) == 1_866_240
    assert counts.step_flops_per_example(conv) == 47_651_328
    assert dp_clip.call_bytes(12, 155_530) == 4 * 12 * 155_530 + 8 * 155_530


@pytest.mark.parametrize("name", ["p4-linear-cifar10", "p4-cnn-cifar10"])
def test_forward_against_cost_analysis(name):
    """XLA counts the forward's multiply-adds as 2 FLOPs each, plus the
    bias, relu and pooling elementwise work; the model count leaves only
    that elementwise work out."""
    from chipbench import program
    cfg = _cfg(name)
    mix = {"local_steps": 1, "schedule": {"kind": "full"}, "eval_every": 1}
    cfg = dict(cfg, dp={"epsilon": 15.0, "delta": 1e-3, "clip_norm": 1.0,
                        "rounds": 100, "sample_rate": 1.0,
                        "per_example_chunk": 0},
               p4={"group_size": 8, "sample_peers": 35, "alpha": 0.5,
                   "beta": 0.5},
               train={"learning_rate": 0.1},
               kernels={"backend": "ref", "autotune": False,
                        "dp_clip_tile": [0, 0], "l1_tile": [0, 0]})
    p = program.build(cfg, mix, harness.model_kind(cfg).trainer_kwargs(cfg))
    params = {k: jax.ShapeDtypeStruct(v, jnp.float32)
              for k, v in program.state_shapes(p).items()}
    x = jax.ShapeDtypeStruct((1, cfg["feat_dim"]), jnp.float32)
    cost = jax.jit(p.trainer.apply_fn).lower(params, x).compile() \
        .cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    want = counts.forward_flops(cfg)
    assert want <= cost["flops"] <= 1.02 * want, (cost["flops"], want)
