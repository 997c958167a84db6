"""Work counts of the benchmark's models and kernels, computed from shapes.

A model's count module is ``counts/<model>.py`` with ``forward_macs(cfg)``.
Model FLOPs of P4's local step count 6 forward-pass units per example and
step (1 unit = 2 x MACs): forward and backward (2 units) of the private and
of the proxy model; each model's forward logits serve as the other's
distillation target. Recomputed forwards and the extra metrics step do not
count.
"""
from __future__ import annotations

import importlib.util
import os

UNITS_PER_EXAMPLE_STEP = 6
HERE = os.path.dirname(os.path.abspath(__file__))


def model(cfg, here: str = HERE):
    """The configuration's count module, ``counts/<model>.py``."""
    path = os.path.join(here, cfg["model"] + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_counts_" + cfg["model"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def forward_flops(cfg, here: str = HERE) -> int:
    return 2 * model(cfg, here).forward_macs(cfg)


def step_flops_per_example(cfg, here: str = HERE) -> int:
    return UNITS_PER_EXAMPLE_STEP * forward_flops(cfg, here)
