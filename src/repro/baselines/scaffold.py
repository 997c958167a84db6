"""DP-SCAFFOLD (Noble et al. [40]): FedAvg + control variates correcting
client drift under heterogeneity; DP noise on the clipped per-example
gradients, RDP-accounted toward the honest-but-curious server.

Engine form: state carries the global model plus the global/per-client
control variates; ``local_update`` runs the drift-corrected DP local steps
and the option-II control-variate update, ``aggregate`` means both back.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.baselines import common
from repro.config import DPConfig
from repro.core import dp as dp_lib
from repro.engine import (Engine, FederatedData, FullParticipation,
                          PrivacyLedger, Strategy, register_strategy,
                          runtime_sigma)


@register_strategy("scaffold")
@dataclass(eq=False)
class ScaffoldStrategy(Strategy):
    feat_dim: int = 0
    num_classes: int = 2
    lr: float = 0.5          # matches the module train() default
    clip: float = 1.0
    sigma: float = 0.0
    local_steps: int = 2

    def __post_init__(self):
        self.specs, self.apply_fn = common.make_model(self.feat_dim,
                                                      self.num_classes)

    def init(self, key, data: FederatedData, batch_size):
        gp = jax.tree_util.tree_map(
            lambda t: t[0], common.init_clients(self.specs, key, 1))
        zeros = jax.tree_util.tree_map(jnp.zeros_like, gp)
        return {"global": gp, "c_global": zeros,
                "c_clients": common.broadcast_like(zeros, data.num_clients)}

    def local_update_keyed(self, state, xs, ys, r, keys):
        M = ys.shape[0]
        params0 = common.broadcast_like(state["global"], M)
        c_global = state["c_global"]

        def one(p0, ci, x, y, k):
            def body(pp, i):
                g = common.client_grad(
                    self.apply_fn, pp, x, y, jax.random.fold_in(k, i),
                    dp_cfg=DPConfig(clip_norm=self.clip),
                    sigma=runtime_sigma(self.sigma))
                # SCAFFOLD drift correction: g - c_i + c
                corr = jax.tree_util.tree_map(lambda gg, cc, cg: gg - cc + cg,
                                              g, ci, c_global)
                return common.sgd_update(pp, corr, self.lr), None
            pK, _ = jax.lax.scan(body, p0, jnp.arange(self.local_steps))
            # option II control-variate update
            new_ci = jax.tree_util.tree_map(
                lambda cc, cg, a, b: cc - cg + (a - b) / (self.local_steps * self.lr),
                ci, c_global, p0, pK)
            return pK, new_ci

        newp, newc = jax.vmap(one)(params0, state["c_clients"], xs, ys, keys)
        return {"clients": newp, "c_clients": newc,
                "c_global": c_global}, {}

    def aggregate(self, mid, r, key):
        return {"global": common.tree_mean(mid["clients"]),
                "c_global": common.tree_mean(mid["c_clients"]),
                "c_clients": mid["c_clients"]}

    # ------------------------------------------------------- sharded engine
    # The carry mixes a client-stacked leaf (c_clients) with replicated
    # server leaves (global, c_global): state_client_stacked stays True and
    # the exact-size spec match shards only the (M, ...) leaf. The mid-round
    # tree swaps "global" for the trained "clients" stack, so the default
    # gather round-trip cannot be reused — these hooks gather the two
    # stacked subtrees explicitly and run the single-device means verbatim
    # (bit-exact), keeping c_clients shard-resident throughout.

    def sharded_aggregate(self, mid, r, key, ctx):
        return {"global": common.tree_mean(ctx.gather(mid["clients"])),
                "c_global": common.tree_mean(ctx.gather(mid["c_clients"])),
                "c_clients": mid["c_clients"]}

    def merge_participation(self, prev_state, new_state, mask):
        """Absent clients keep their control variate; the global quantities
        are cohort-weighted in ``aggregate_masked``."""
        sel = lambda o, n: jnp.where(
            mask.reshape((-1,) + (1,) * (n.ndim - 1)) > 0, n, o)
        out = dict(new_state)
        out["c_clients"] = jax.tree_util.tree_map(
            sel, prev_state["c_clients"], new_state["c_clients"])
        return out

    def aggregate_masked(self, mid, r, key, mask):
        w = mask / jnp.maximum(jnp.sum(mask), 1.0)
        wmean = lambda stacked: jax.tree_util.tree_map(
            lambda t: jnp.einsum("m...,m->...", t, w), stacked)
        return {"global": wmean(mid["clients"]),
                "c_global": wmean(mid["c_clients"]),
                "c_clients": mid["c_clients"]}

    def sharded_aggregate_masked(self, mid, r, key, ctx, mask, local_mask):
        w = mask / jnp.maximum(jnp.sum(mask), 1.0)
        wmean = lambda stacked: jax.tree_util.tree_map(
            lambda t: jnp.einsum("m...,m->...", t, w), stacked)
        return {"global": wmean(ctx.gather(mid["clients"])),
                "c_global": wmean(ctx.gather(mid["c_clients"])),
                "c_clients": mid["c_clients"]}

    def eval_params(self, state):
        return state["global"]

    def evaluate(self, state, test_x, test_y):
        params = common.broadcast_like(state["global"], test_y.shape[0])
        return common.evaluate_clients(self.apply_fn, params, test_x, test_y)


def train(train_x, train_y, test_x, test_y, *, rounds: int = 100, lr: float = 0.5,
          batch_size: int = 32, seed: int = 0, eval_every: int = 20,
          epsilon: float = 15.0, delta: float = None, clip: float = 1.0,
          local_steps: int = 2, dp: bool = True, schedule=None):
    M, R = train_y.shape[:2]
    feat, classes = train_x.shape[-1], int(jnp.max(jnp.asarray(train_y))) + 1
    delta = delta or 1.0 / R
    schedule = schedule or FullParticipation()
    q = batch_size / R
    q_eff = q * schedule.client_fraction(M)
    sigma = (dp_lib.calibrate_sigma(epsilon, delta, q_eff, rounds * local_steps)
             if dp else 0.0)
    ledger = (PrivacyLedger(sigma=sigma, delta=delta, sample_rate=q,
                            client_rate=schedule.client_fraction(M),
                            local_steps=local_steps) if dp else None)

    strategy = ScaffoldStrategy(feat_dim=feat, num_classes=classes, lr=lr,
                                clip=clip, sigma=sigma, local_steps=local_steps)
    data = FederatedData(train_x, train_y, test_x, test_y)
    state, hist = Engine(strategy, eval_every=eval_every, schedule=schedule,
                         ledger=ledger).fit(
        data, rounds=rounds, key=jax.random.PRNGKey(seed),
        batch_size=batch_size)
    return state["global"], hist, sigma
