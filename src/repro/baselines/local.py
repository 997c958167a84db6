"""Local training baseline: each client trains alone (paper §4.2.1).

The paper runs it WITHOUT DP (local data never leaves the device, so no noise
is needed) — the relevant comparison for Fig. 7.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.baselines import common
from repro.engine import (Engine, FederatedData, Strategy, register_strategy,
                          runtime_sigma)


@register_strategy("local")
@dataclass(eq=False)
class LocalStrategy(Strategy):
    feat_dim: int = 0
    num_classes: int = 2
    lr: float = 0.5
    dp_cfg: Optional[object] = None
    sigma: float = 0.0
    kernels: Optional[object] = None

    def __post_init__(self):
        self.specs, self.apply_fn = common.make_model(self.feat_dim,
                                                      self.num_classes)

    def init(self, key, data: FederatedData, batch_size):
        return common.init_clients(self.specs, key, data.num_clients)

    def local_update_keyed(self, params, xs, ys, r, keys):
        def one(p, x, y, k):
            g = common.client_grad(self.apply_fn, p, x, y, k,
                                   dp_cfg=self.dp_cfg,
                                   sigma=runtime_sigma(self.sigma),
                                   kernels=self.kernels)
            return common.sgd_update(p, g, self.lr)
        return jax.vmap(one)(params, xs, ys, keys), {}

    def local_update(self, params, xs, ys, r, key):
        M = ys.shape[0]
        return self.local_update_keyed(params, xs, ys, r,
                                       jax.random.split(key, M))

    def eval_params(self, state):
        return state


def train(train_x, train_y, test_x, test_y, *, rounds: int = 100, lr: float = 0.5,
          batch_size: int = 32, seed: int = 0, eval_every: int = 20,
          dp_cfg=None, sigma: float = 0.0, schedule=None, kernels=None):
    feat, classes = train_x.shape[-1], int(jnp.max(jnp.asarray(train_y))) + 1
    strategy = LocalStrategy(feat_dim=feat, num_classes=classes, lr=lr,
                             dp_cfg=dp_cfg, sigma=sigma, kernels=kernels)
    data = FederatedData(train_x, train_y, test_x, test_y)
    state, hist = Engine(strategy, eval_every=eval_every,
                         schedule=schedule).fit(
        data, rounds=rounds, key=jax.random.PRNGKey(seed),
        batch_size=batch_size)
    return state, hist
