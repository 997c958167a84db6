"""DP-FedAvg (McMahan et al. [35] + record-level DP toward an honest-but-
curious server). Noise is RDP-accounted for the subsampled Gaussian over T
rounds with user sampling ratio u (paper §4.2.1 / Noble et al.).

Engine form: state is the single global model; ``local_update`` broadcasts it
to M clients and runs K DP local steps, ``aggregate`` draws the user cohort
mask on device and takes the cohort-weighted mean back to a global model.
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


@register_strategy("fedavg")
@dataclass(eq=False)
class FedAvgStrategy(Strategy):
    feat_dim: int = 0
    num_classes: int = 2
    lr: float = 0.5
    clip: float = 1.0
    sigma: float = 0.0
    local_steps: int = 1
    user_ratio: float = 1.0
    # sharded cohort reduction: "psum" = per-shard partial weighted sums
    # tree-reduced with one lax.psum (no (M, ...) stack ever materializes on
    # a single slice — bit-close to the gather path, verified in
    # tests/test_sharded_engine.py); "gather" = all_gather → single-device
    # aggregate verbatim (bit-exact but O(M) memory per slice)
    reduce: str = "psum"

    def __post_init__(self):
        self.specs, self.apply_fn = common.make_model(self.feat_dim,
                                                      self.num_classes)

    def init(self, key, data: FederatedData, batch_size):
        return jax.tree_util.tree_map(
            lambda t: t[0], common.init_clients(self.specs, key, 1))

    def local_update_keyed(self, gp, xs, ys, r, keys):
        params = common.broadcast_like(gp, ys.shape[0])

        def one(p, x, y, k):
            def body(pp, i):
                g = common.client_grad(
                    self.apply_fn, pp, x, y, jax.random.fold_in(k, i),
                    dp_cfg=DPConfig(clip_norm=self.clip),
                    sigma=runtime_sigma(self.sigma))
                return common.sgd_update(pp, g, self.lr), None
            p2, _ = jax.lax.scan(body, p, jnp.arange(self.local_steps))
            return p2

        return jax.vmap(one)(params, xs, ys, keys), {}

    def state_client_stacked(self, state) -> bool:
        # server-style carry: ONE global model, replicated across the client
        # mesh; only the mid-round (M, ...) local-update stacks are sharded
        return False

    def _user_mask(self, key, M):
        """Strategy-level user sampling draw — shared by the single-device
        aggregate and the psum path so both realize the identical cohort.
        The empty draw falls back to one random participant so the global
        model is always defined."""
        k1, k2 = jax.random.split(key)
        mask = (jax.random.uniform(k1, (M,)) < self.user_ratio).astype(jnp.float32)
        fallback = jnp.zeros((M,)).at[jax.random.randint(k2, (), 0, M)].set(1.0)
        return jnp.where(jnp.sum(mask) > 0, mask, fallback)

    def aggregate(self, clients, r, key):
        """Strategy-level user sampling (the pre-schedule path; NOT
        amplification-accounted — prefer an engine ClientSampling schedule
        for that)."""
        M = jax.tree_util.tree_leaves(clients)[0].shape[0]
        return self.aggregate_masked(clients, r, key, self._user_mask(key, M))

    def merge_participation(self, prev_state, new_state, mask):
        # server-style state: the cohort is applied as aggregation weights,
        # nothing to freeze per client
        return new_state

    def aggregate_masked(self, clients, r, key, mask):
        """Engine-drawn cohort replaces the strategy's own user sampling:
        the global model is the cohort-weighted mean (the schedule guarantees
        a non-empty cohort)."""
        w = mask / jnp.maximum(jnp.sum(mask), 1.0)
        return jax.tree_util.tree_map(
            lambda n: jnp.einsum("m...,m->...", n, w), clients)

    # ------------------------------------------------------- sharded engine
    def _psum_mean(self, clients, w_full, ctx):
        """Cohort mean as a psum tree-reduction: every shard contracts its
        own client rows against its slice of the full (M,) weight vector
        (padded slots carry weight 0), then one lax.psum combines the
        partials. The (M, ...) stack never materializes on a slice — the
        reduction is O(model) per shard instead of the gather's O(M·model)."""
        local_w = ctx.rows(w_full)
        partial = jax.tree_util.tree_map(
            lambda t: jnp.einsum("m...,m->...", t, local_w), clients)
        return jax.tree_util.tree_map(
            lambda t: jax.lax.psum(t, ctx.axis), partial)

    def sharded_aggregate(self, clients, r, key, ctx):
        if self.reduce == "gather":
            full = ctx.gather(clients)
            return ctx.scatter_like(self.aggregate(full, r, key), full)
        # identical (replicated) user-sampling draw to the single-device path
        mask = self._user_mask(key, ctx.M)
        return self._psum_mean(clients, mask / jnp.maximum(jnp.sum(mask), 1.0),
                               ctx)

    def sharded_aggregate_masked(self, clients, r, key, ctx, mask, local_mask):
        if self.reduce == "gather":
            full = ctx.gather(clients)
            return ctx.scatter_like(self.aggregate_masked(full, r, key, mask),
                                    full)
        return self._psum_mean(clients, mask / jnp.maximum(jnp.sum(mask), 1.0),
                               ctx)

    def eval_params(self, state):
        return state  # unused: evaluate() broadcasts

    def evaluate(self, state, test_x, test_y):
        params = common.broadcast_like(state, test_y.shape[0])
        return common.evaluate_clients(self.apply_fn, params, test_x, test_y)


def train(train_x, train_y, test_x, test_y, *, rounds: int = 100, lr: float = 0.5,
          batch_size: int = 32, seed: int = 0, eval_every: int = 20,
          epsilon: float = 15.0, delta: float = None, clip: float = 1.0,
          user_ratio: float = 1.0, local_steps: int = 1, dp: bool = True,
          schedule=None):
    """``schedule`` (a RoundSchedule) moves user sampling into the engine;
    σ is then RDP-calibrated at the amplified rate q_batch · q_client, and the
    returned ``History.metrics`` carries the cumulative (ε, δ) per eval round."""
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

    strategy = FedAvgStrategy(feat_dim=feat, num_classes=classes, lr=lr,
                              clip=clip, sigma=sigma, local_steps=local_steps,
                              user_ratio=user_ratio)
    data = FederatedData(train_x, train_y, test_x, test_y)
    state, hist = Engine(strategy, eval_every=eval_every, schedule=schedule,
                         ledger=ledger).fit(
        data, rounds=rounds, key=jax.random.PRNGKey(seed),
        batch_size=batch_size)
    return state, hist, sigma
