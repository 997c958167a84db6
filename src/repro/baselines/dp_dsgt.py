"""DP-DSGT (Bayrooti et al. [4]): differentially-private decentralized SGD
with gradient tracking — consensus-seeking (one shared solution), which is
exactly what the paper argues fails under non-IID tasks.

  x_i ← Σ_j W_ij x̃_j − lr · y_i
  y_i ← Σ_j W_ij ỹ_j + (g_i(x⁺) − g_i(x))

where x̃/ỹ are the DP-noised (clipped) shared quantities and W is the
mixing matrix of the communication graph.

The paper's W is the ring with self weight 1/2 and 1/4 per neighbor; here W
comes from the topology subsystem (``repro.topology``), so DSGT runs over
any graph family — ring / torus / expander / Erdős–Rényi / time-varying
gossip — with in-jit link faults. ``topology=None`` builds the historical
ring at ``init``, and the compiled ring plan's mixing arithmetic is
bit-identical to the pre-refactor ``_ring_mix`` (the ring is literally the
special case of the general sparse mixing step — locked down in
``tests/test_topology.py``).

Engine form: state = {params, tracker, last gradients}; the tracker is
bootstrapped in ``init`` from a first on-device batch draw.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.baselines import common
from repro.config import DPConfig
from repro.core import dp as dp_lib
from repro.engine import (Engine, FederatedData, FullParticipation,
                          PrivacyLedger, Strategy, register_strategy,
                          runtime_sigma, sample_client_batches)
from repro.engine.schedule import ResidentCtx
from repro.engine.strategy import current_halos


@register_strategy("dp_dsgt")
@dataclass(eq=False)
class DPDSGTStrategy(Strategy):
    feat_dim: int = 0
    num_classes: int = 2
    lr: float = 0.3
    clip: float = 1.0
    sigma: float = 0.0
    # communication graph (repro.topology.Topology / TimeVaryingTopology,
    # hashable by value → part of the chunk-cache fingerprint); None builds
    # the paper's ring over the run's M clients at init
    topology: Optional[object] = None

    def __post_init__(self):
        self.specs, self.apply_fn = common.make_model(self.feat_dim,
                                                      self.num_classes)

    # ------------------------------------------------------------- topology
    def _ensure_plan(self, M: int) -> None:
        from repro.topology.graphs import ring
        from repro.topology.mixing import make_plan
        if self.topology is None:
            self.topology = ring(M)          # the paper's default graph
        if self.topology.M != M:
            raise ValueError(
                f"topology is over {self.topology.M} clients but the run has "
                f"M={M}")
        if self._mix_plan is None or self._mix_plan.topology is not self.topology:
            self._mix_plan = make_plan(self.topology)

    # ------------------------------------------------------------ gradients
    def _grads_keyed(self, params, xs, ys, keys):
        def one(p, x, y, k):
            return common.client_grad(self.apply_fn, p, x, y, k,
                                      dp_cfg=DPConfig(clip_norm=self.clip),
                                      sigma=runtime_sigma(self.sigma))
        return jax.vmap(one)(params, xs, ys, keys)

    @property
    def _push_sum(self) -> bool:
        return bool(self._mix_plan is not None and self._mix_plan.push_sum)

    def align_push_sum_state(self, state):
        """Reconcile a carried state across a topology swap (the learned-
        graph drivers re-estimate between ``Engine.fit`` segments): entering
        a push-sum plan grows the (M,) weight-scalar leaf at 1 (the carried
        x is already unbiased), leaving one folds the bias back into x
        (x ← x/w) and drops the leaf."""
        from repro.topology.mixing import push_sum_debias
        if self._push_sum and "w" not in state:
            M = jax.tree_util.tree_leaves(state["x"])[0].shape[0]
            state = dict(state, w=jnp.ones((M,), jnp.float32))
        elif not self._push_sum and "w" in state:
            state = dict(state)
            state["x"] = push_sum_debias(state["x"], state.pop("w"))
        return state

    # ---------------------------------------------------------------- hooks
    def init(self, key, data: FederatedData, batch_size):
        self._ensure_plan(data.num_clients)
        k1, k2, k3 = jax.random.split(key, 3)
        x_params = common.init_clients(self.specs, k1, data.num_clients)
        xs0, ys0 = sample_client_batches(data.train_x, data.train_y, k2,
                                         batch_size)
        y_track = self._grads_keyed(x_params, xs0, ys0,
                                    jax.random.split(k3, data.num_clients))
        # distinct buffers: the engine donates the carry, and XLA rejects the
        # same buffer appearing twice in a donated argument
        state = {"x": x_params, "y": y_track,
                 "g": jax.tree_util.tree_map(jnp.copy, y_track)}
        return self.align_push_sum_state(state)

    def _gossip_round(self, state, xs, ys, r, key, ctx):
        """One communication round in the layout ``ctx``: one realized graph,
        so both mixes share the round's fault realization (drawn in-jit off
        key's fault stream). Under a push-sum plan (directed/learned W) the
        weight scalar rides the x mix as a joint leaf (gradient-push):
        gradients are taken at the de-biased z = x/w, and the tracker mixes
        with the same matrix. Gradients are per client, keyed by the layout's
        rows of the global key split. On a client shard, the mixes consume
        the boundary rows ``sharded_prefetch`` exchanged at the end of the
        previous round — valid because both read the round-START x and y."""
        from repro.topology.mixing import push_sum_debias
        halos = current_halos() or {}
        if self._push_sum:
            mixed = ctx.mix(self, {"x": state["x"], "w": state["w"]}, r, key,
                            halos.get("xw"))
            x_new, w_new = mixed["x"], mixed["w"]
        else:
            x_new = ctx.mix(self, state["x"], r, key, halos.get("x"))
        x_new = jax.tree_util.tree_map(lambda x, y: x - self.lr * y,
                                       x_new, state["y"])
        z = push_sum_debias(x_new, w_new) if self._push_sum else x_new
        g_new = self._grads_keyed(z, xs, ys, ctx.client_keys(key))
        y_new = ctx.mix(self, state["y"], r, key, halos.get("y"))
        y_new = jax.tree_util.tree_map(lambda y, a, b: y + a - b,
                                       y_new, g_new, state["g"])
        out = {"x": x_new, "y": y_new, "g": g_new}
        if self._push_sum:
            out["w"] = w_new
        return out, {}

    def local_update(self, state, xs, ys, r, key):
        return self._gossip_round(state, xs, ys, r, key,
                                  ResidentCtx(ys.shape[0]))

    def sharded_local_update(self, state, xs, ys, r, key, ctx):
        return self._gossip_round(state, xs, ys, r, key, ctx)

    def paged_local_update(self, state, xs, ys, r, key, pctx):
        return self._gossip_round(state, xs, ys, r, key, pctx)

    def sharded_prefetch(self, state, ctx):
        """Issue the next round's boundary-row ppermutes from the end-of-
        round state (x and y are mixed at round start, so the rows a shard
        will need are known as soon as the round's update lands). Only the
        halo path prefetches — local/gather/identity paths have nothing to
        overlap. Under push-sum the x halo carries the weight scalar too
        (``_gossip_round`` mixes them jointly)."""
        from repro.topology.mixing import select_mix_path, halo_start
        if self._mix_plan is None:
            return None
        if select_mix_path(self._mix_plan, ctx) != "halo":
            return None
        if self._push_sum:
            return {"xw": halo_start({"x": state["x"], "w": state["w"]},
                                     self._mix_plan, ctx),
                    "y": halo_start(state["y"], self._mix_plan, ctx)}
        return {"x": halo_start(state["x"], self._mix_plan, ctx),
                "y": halo_start(state["y"], self._mix_plan, ctx)}

    def eval_params(self, state):
        if "w" in state:
            from repro.topology.mixing import push_sum_debias
            return push_sum_debias(state["x"], state["w"])
        return state["x"]

    # ------------------------------------------------------ byte accounting
    def log_communication(self, net, state, r: int, mask=None,
                          phase_key=None, faults=None) -> None:
        """§4.5-style gossip accounting: every alive directed edge carries
        the sender's BOTH shared quantities — the noised model x̃ and the
        gradient tracker ỹ (one exchange per round mixes both, see
        ``_gossip_round``). Absent cohort members (sampling schedule) and
        dropped links / churned nodes (the round's fault realization,
        re-derived from ``phase_key``) contribute zero bytes. Under a
        correlated fault process (``faults`` — the engine's replayed
        ``HostFaults``) the realized keep matrix supersedes the topology's
        i.i.d. draw, mirroring the traced mix."""
        if self._mix_plan is None or self.topology is None:
            return
        keep = None
        if faults is not None:
            keep = faults.keep
        elif self._mix_plan.faulty and phase_key is not None:
            from repro.topology.faults import host_fault_masks
            keep, _ = host_fault_masks(phase_key, r, 1, self._mix_plan.M,
                                       self._mix_plan.drop_prob,
                                       self._mix_plan.churn_prob)
        from repro.topology.accounting import log_gossip_round
        log_gossip_round(net, self.topology,
                         {"x": state["x"], "y": state["y"]}, r, mask=mask,
                         keep=keep)


def train(train_x, train_y, test_x, test_y, *, rounds: int = 100, lr: float = 0.3,
          batch_size: int = 32, seed: int = 0, eval_every: int = 20,
          epsilon: float = 15.0, delta: float = None, clip: float = 1.0,
          dp: bool = True, schedule=None, topology=None, network=None):
    M, R = train_y.shape[:2]
    feat, classes = train_x.shape[-1], int(jnp.max(jnp.asarray(train_y))) + 1
    delta = delta or 1.0 / R
    schedule = schedule or FullParticipation()
    sigma = (dp_lib.noble_sigma(epsilon, delta, sample_rate=batch_size / R,
                                rounds=rounds) if dp else 0.0)
    # σ stays Eq. 12 (Noble); the ledger reports the RDP-accounted spend it
    # actually induces (amplified by the schedule's client fraction)
    ledger = (PrivacyLedger(sigma=sigma, delta=delta, sample_rate=batch_size / R,
                            client_rate=schedule.client_fraction(M))
              if dp else None)

    strategy = DPDSGTStrategy(feat_dim=feat, num_classes=classes, lr=lr,
                              clip=clip, sigma=sigma if dp else 0.0,
                              topology=topology)
    data = FederatedData(train_x, train_y, test_x, test_y)
    state, hist = Engine(strategy, eval_every=eval_every, schedule=schedule,
                         ledger=ledger, network=network).fit(
        data, rounds=rounds, key=jax.random.PRNGKey(seed),
        batch_size=batch_size)
    return state["x"], hist, sigma
