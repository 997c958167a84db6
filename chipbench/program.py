"""The system under test, built through its public path from a cell's
configuration and traffic files: ``P4Trainer`` for the models, the DP step
and Phase 1, ``P4Strategy`` + ``Engine`` + ``make_schedule`` for the round
loop. Nothing here computes; it assembles the program's objects and reads
the distance matrix its Phase 1 hands on."""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Any, Optional

import numpy as np


@dataclass
class Program:
    trainer: Any
    strategy: Any
    bootstrap: Any        # Engine for the full-batch bootstrap (no groups)
    engine: Any           # Engine for co-training, with the cell's schedule
    run_cfg: Any


def run_config(cfg, traffic, kernels: Optional[dict] = None):
    from repro.config import (DPConfig, KernelConfig, P4Config, RunConfig,
                              ScheduleConfig, TrainConfig)
    dp, p4, k = cfg["dp"], cfg["p4"], dict(cfg["kernels"], **(kernels or {}))
    return RunConfig(
        dp=DPConfig(enabled=True, epsilon=dp["epsilon"], delta=dp["delta"],
                    clip_norm=dp["clip_norm"], rounds=dp["rounds"],
                    sample_rate=dp["sample_rate"],
                    local_steps=traffic["local_steps"],
                    per_example_chunk=dp["per_example_chunk"]),
        p4=P4Config(group_size=p4["group_size"],
                    sample_peers=p4["sample_peers"], alpha=p4["alpha"],
                    beta=p4["beta"]),
        train=TrainConfig(optimizer="sgd",
                          learning_rate=cfg["train"]["learning_rate"]),
        kernels=KernelConfig(backend=k["backend"], autotune=k["autotune"],
                             dp_clip_tile=tuple(k["dp_clip_tile"]),
                             l1_tile=tuple(k["l1_tile"])),
        schedule=ScheduleConfig(accountant="none", **traffic["schedule"]))


def build(cfg, traffic, trainer_kwargs, kernels: Optional[dict] = None
          ) -> Program:
    """The program for ``cfg`` under ``traffic``; ``trainer_kwargs`` (the
    model kind's) name its model to ``P4Trainer``."""
    from repro.core.p4 import P4Strategy, P4Trainer
    from repro.engine import Engine, make_schedule
    run = run_config(cfg, traffic, kernels)
    trainer = P4Trainer(cfg=run, **trainer_kwargs)
    strategy = P4Strategy(trainer=trainer)
    every = traffic["eval_every"]
    return Program(trainer=trainer, strategy=strategy,
                   bootstrap=Engine(strategy, eval_every=every),
                   engine=Engine(strategy, eval_every=every,
                                 schedule=make_schedule(run.schedule)),
                   run_cfg=run)


def federated(data):
    from repro.engine import FederatedData
    return FederatedData(data["train_x"], data["train_y"], data["test_x"],
                         data["test_y"])


@contextlib.contextmanager
def seen_distances(out):
    """Within the block, ``out["dist"]`` receives the distance matrix that
    ``P4Trainer.form_groups`` hands to its greedy procedure: the program's
    own Phase-1 distances, read without a second computation."""
    import repro.core.p4 as p4_module
    greedy = p4_module.greedy_group_formation

    def seen(dist, *args, **kwargs):
        out["dist"] = np.array(dist)
        return greedy(dist, *args, **kwargs)
    p4_module.greedy_group_formation = seen
    try:
        yield
    finally:
        p4_module.greedy_group_formation = greedy


def state_shapes(program):
    """Leaf shapes of one client's model as the program declares them."""
    from repro.models.module import abstract_params
    return {k: tuple(v.shape)
            for k, v in abstract_params(program.trainer.specs).items()}
