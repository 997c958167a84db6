"""ProxyFL (Kalra et al. [25]): each client keeps a private model and a proxy
model trained by deep mutual learning; proxies circulate over a DIRECTED
EXPONENTIAL graph (at round t, client i sends to (i + 2^(t mod ⌈log2 M⌉)) mod
M) with DP-SGD on the proxy. The paper's closest decentralized competitor —
no similarity grouping, no handcrafted-feature requirement.

Engine form: the exponential-graph shift is computed from the traced round
index, so the whole exchange schedule lives inside the scanned round body.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.baselines import common
from repro.core import distill, dp as dp_lib
from repro.engine import (Engine, FederatedData, FullParticipation,
                          PrivacyLedger, Strategy, register_strategy,
                          runtime_sigma)


@register_strategy("proxyfl")
@dataclass(eq=False)
class ProxyFLStrategy(Strategy):
    feat_dim: int = 0
    num_classes: int = 2
    lr: float = 0.5
    clip: float = 1.0
    sigma: float = 0.0
    alpha: float = 0.5
    beta: float = 0.5

    def __post_init__(self):
        self.specs, self.apply_fn = common.make_model(self.feat_dim,
                                                      self.num_classes)

    def init(self, key, data: FederatedData, batch_size):
        M = data.num_clients
        return {"private": common.init_clients(self.specs, key, M),
                "proxy": common.init_clients(self.specs,
                                             jax.random.fold_in(key, 1), M)}

    def local_update_keyed(self, state, xs, ys, r, keys):
        apply_fn = self.apply_fn

        def one(theta, w, x, y, k):
            w_logits = apply_fn(w, x)

            def private_obj(p):
                return distill.private_loss(apply_fn(p, x), w_logits, y, self.beta)
            g_t = jax.grad(private_obj)(theta)

            def proxy_obj(p, b):
                tgt = apply_fn(jax.lax.stop_gradient(theta), b["x"])
                return distill.proxy_loss(apply_fn(p, b["x"]), tgt, b["y"],
                                          self.alpha)
            if self.sigma > 0:
                g_w = dp_lib.dp_gradients(proxy_obj, w, {"x": x, "y": y}, k,
                                          clip=self.clip,
                                          sigma=runtime_sigma(self.sigma))
            else:
                g_w = jax.grad(lambda p: proxy_obj(p, {"x": x, "y": y}))(w)
            return (common.sgd_update(theta, g_t, self.lr),
                    common.sgd_update(w, g_w, self.lr))

        private, proxy = jax.vmap(one)(state["private"], state["proxy"], xs, ys,
                                       keys)
        return {"private": private, "proxy": proxy}, {}

    def aggregate(self, state, r, key):
        """Receive neighbor's proxy (directed exponential graph), average.

        Under the sharded engine the default ``Strategy.sharded_aggregate``
        gathers the full (M, ...) stacks and runs this verbatim — the
        exponential-graph shift crosses shard boundaries, and the gather
        keeps the modulus at the TRUE client count (inside the shard region
        the local leading dim would be m, silently shrinking the graph)."""
        # M is a static shape, so log2m is a trace-time constant — derived
        # here (not in init) so engine-resumed external states work too
        M = jax.tree_util.tree_leaves(state["proxy"])[0].shape[0]
        log2m = max(1, math.ceil(math.log2(M)))
        shift = jnp.left_shift(1, jnp.mod(r, log2m))
        received = jax.tree_util.tree_map(
            lambda t: jnp.roll(t, shift, axis=0), state["proxy"])
        proxy = jax.tree_util.tree_map(lambda a, b: 0.5 * (a + b),
                                       state["proxy"], received)
        return {"private": state["private"], "proxy": proxy}

    def eval_params(self, state):
        return state["private"]


def train(train_x, train_y, test_x, test_y, *, rounds: int = 100, lr: float = 0.5,
          batch_size: int = 32, seed: int = 0, eval_every: int = 20,
          epsilon: float = 15.0, delta: float = None, clip: float = 1.0,
          alpha: float = 0.5, beta: float = 0.5, dp: bool = True,
          schedule=None):
    M, R = train_y.shape[:2]
    feat, classes = train_x.shape[-1], int(jnp.max(jnp.asarray(train_y))) + 1
    delta = delta or 1.0 / R
    schedule = schedule or FullParticipation()
    sigma = (dp_lib.noble_sigma(epsilon, delta, sample_rate=batch_size / R,
                                rounds=rounds, local_steps=1) if dp else 0.0)
    ledger = (PrivacyLedger(sigma=sigma, delta=delta, sample_rate=batch_size / R,
                            client_rate=schedule.client_fraction(M))
              if dp else None)

    strategy = ProxyFLStrategy(feat_dim=feat, num_classes=classes, lr=lr,
                               clip=clip, sigma=sigma, alpha=alpha, beta=beta)
    data = FederatedData(train_x, train_y, test_x, test_y)
    state, hist = Engine(strategy, eval_every=eval_every, schedule=schedule,
                         ledger=ledger).fit(
        data, rounds=rounds, key=jax.random.PRNGKey(seed),
        batch_size=batch_size)
    return state["private"], hist, sigma
