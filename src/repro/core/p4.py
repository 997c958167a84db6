"""P4 — the paper's full algorithm (Phases 1+2), plus its LM-scale form.

Small-scale (paper-faithful): ``P4Trainer`` simulates M clients as stacked
(M, ...) parameter pytrees; local steps are vmapped across clients, group
aggregation is a segment-mean over proxy parameters, grouping is the greedy
decentralized procedure on first-step weights.

A language model (``model="lm"``, ``core/lm.py``) takes the same path: each
client's rows are int32 token sequences of s + 1 tokens (the model reads
the first s, each position's label is its next token, and the label rows
``y`` carry nothing; ``token_rows`` maps rows stored otherwise to these),
one example is one sequence, Eqs. 8-9
are applied per token and averaged over each sequence, the proxy's DP
gradient takes the per-layer norm route (``dp.dp_layer_clipped_sum``), and
every pass over a batch runs in blocks of ``per_example_chunk`` sequences,
so activation memory is bounded by the block; clients are stepped one after
another (``lax.map``), since one client's step fills the chip.

LM-scale (framework feature): ``make_p4_lm_step`` builds one jitted step over
G client *groups* (G = the ``pod`` mesh axis in multi-pod runs — DESIGN.md §4):
parameters carry a leading G dim sharded over ``pod``; vmap over G makes every
gradient reduction group-internal by construction, exactly the paper's
"communicate only within your group" topology.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import DPConfig, P4Config, RunConfig, TrainConfig
from repro.core import distill, dp as dp_lib
from repro.core.grouping import (flatten_clients, greedy_group_formation,
                                 group_ids, pairwise_l1, random_groups)
from repro.core.small_models import accuracy, linear_apply, linear_specs, make_cnn
from repro.engine import (Engine, FederatedData, PrivacyLedger, ShardedEngine,
                          Strategy, make_schedule, register_strategy,
                          runtime_sigma)
from repro.models.module import init_params
from repro.obs import layer, span
from repro.utils.pytree import tree_flatten_concat, tree_unflatten_concat


def group_mean(stacked_tree, ids: jnp.ndarray, num_groups: int):
    """Per-group mean of a stacked (M, ...) pytree, broadcast back to (M, ...)."""
    M = ids.shape[0]
    counts = jax.ops.segment_sum(jnp.ones((M,), jnp.float32), ids, num_groups)

    def f(x):
        sums = jax.ops.segment_sum(x, ids, num_groups)
        mean = sums / counts.reshape((-1,) + (1,) * (x.ndim - 1))
        return mean[ids].astype(x.dtype)

    return jax.tree_util.tree_map(f, stacked_tree)


def masked_group_mean(stacked_tree, ids: jnp.ndarray, num_groups: int, mask):
    """Group mean over the participating cohort only: absent members neither
    contribute to nor receive their group's mean (their slot keeps its own
    value). A group with no present members is left untouched."""
    counts = jax.ops.segment_sum(mask, ids, num_groups)

    def f(x):
        w = mask.reshape((-1,) + (1,) * (x.ndim - 1))
        sums = jax.ops.segment_sum(x * w, ids, num_groups)
        denom = jnp.maximum(counts, 1.0).reshape((-1,) + (1,) * (x.ndim - 1))
        mean = (sums / denom)[ids].astype(x.dtype)
        return jnp.where(w > 0, mean, x)

    return jax.tree_util.tree_map(f, stacked_tree)


@dataclass(eq=False)  # hashable by identity (methods are jitted with static self)
class P4Trainer:
    feat_dim: int
    num_classes: int
    cfg: RunConfig
    model: str = "linear"                 # linear | cnn | lm
    cnn_shape: Optional[Tuple[int, int, int]] = None  # (C, H, W) for model=cnn
    lm: Optional[dict] = None   # model=lm: ModelConfig fields (core.lm.lm_config)
    # model=lm: the data's rows (b, ...) -> int32 token rows (b, s + 1), for
    # data stored in another form; None: the rows are token rows
    token_rows: Optional[Callable] = None

    def __post_init__(self):
        self.lm_cfg = None
        if self.model == "linear":
            self.specs = linear_specs(self.feat_dim, self.num_classes)
            self.apply_fn = linear_apply
        elif self.model == "lm":
            from repro.core.lm import lm_config, make_lm
            self.lm_cfg = lm_config(self.lm)
            self.specs, self.apply_fn = make_lm(self.lm_cfg, self.cfg.kernels)
        else:
            self.specs, self.apply_fn = make_cnn(self.cnn_shape, self.num_classes)
        dpc = self.cfg.dp
        if dpc.noise_multiplier > 0:
            self.sigma = dpc.noise_multiplier
        elif dpc.enabled:
            delta = dpc.delta or 1e-3
            self.sigma = dp_lib.noble_sigma(
                dpc.epsilon, delta, sample_rate=dpc.sample_rate,
                rounds=dpc.rounds, local_steps=dpc.local_steps)
        else:
            self.sigma = 0.0

    # ------------------------------------------------------------------
    def init_clients(self, key, M: int):
        """COMMON initialization across clients (standard FL): Phase 1's ℓ1
        metric then measures data-driven weight divergence, not random-init
        distance — with per-client inits the metric is pure noise."""
        k1, k2 = jax.random.split(key)
        def bcast(k):
            p = init_params(self.specs, k)
            return jax.tree_util.tree_map(
                lambda t: jnp.broadcast_to(t[None], (M,) + t.shape), p)
        return {"private": bcast(k1), "proxy": bcast(k2)}

    # ------------------------------------------------------------------
    def _affine_route(self) -> bool:
        """True where the step takes the stacked affine route: per-example
        DP (no microbatches) on a model whose logits are affine in x."""
        dpc = self.cfg.dp
        return (dpc.enabled and not dpc.microbatches
                and self.apply_fn is linear_apply)

    def _ghost_route(self) -> bool:
        """True where the proxy's DP gradient takes the per-layer ghost-norm
        route: per-example DP (no microbatches) on a model whose apply
        carries a layer seam (``small_models.LayerSeam``)."""
        dpc = self.cfg.dp
        return (dpc.enabled and not dpc.microbatches
                and getattr(self.apply_fn, "layer_seam", None) is not None)

    def _proxy_logit_grads(self, proxy_logits, private_logits, y):
        """Each example's gradient of its own proxy loss (Eq. 8) with
        respect to its proxy logits, (B, C)."""
        p4c = self.cfg.p4

        def one_loss(z, t, label):
            return distill.proxy_loss(z[None], t[None], label[None],
                                      p4c.alpha, p4c.distill_temperature)
        return jax.vmap(jax.grad(one_loss))(
            proxy_logits, jax.lax.stop_gradient(private_logits), y)

    def _losses(self, private_logits, proxy_logits, y):
        """The round's metrics: Eqs. 9 and 8 on the given logits."""
        p4c = self.cfg.p4
        return {
            "private_loss": distill.private_loss(private_logits, proxy_logits,
                                                 y, p4c.beta),
            "proxy_loss": distill.proxy_loss(proxy_logits, private_logits, y,
                                             p4c.alpha),
        }

    def _both_logits(self, private, proxy, x):
        """Both affine models' logits from one read of x:
        x·[w_priv | w_prox] + [b_priv | b_prox], split by columns. Formed
        class-major: in the (B, 2C) form the CPU backend fuses the bias add
        differently in a one-client and an eight-client program, and the
        sharded engine's states drift from the single-device engine's by
        more float ulps."""
        C = self.num_classes
        w = jnp.concatenate([private["w"], proxy["w"]], axis=1)
        z = (jnp.einsum("bf,fc->cb", x, w.astype(jnp.float32))
             + jnp.concatenate([private["b"], proxy["b"]])[:, None])
        return z[:C].T, z[C:].T

    def _client_step(self, private, proxy, x, y, key, lr):
        """One local step for ONE client (vmapped across M)."""
        if self._affine_route():
            return self._affine_step(private, proxy, x, y, key, lr)
        if self.lm_cfg is not None:
            return self._lm_step(private, proxy, x, y, key, lr)
        p4c, dpc = self.cfg.p4, self.cfg.dp
        ghost = self._ghost_route()

        private_logits = self.apply_fn(private, x)
        if ghost:
            # proxy model: DP gradient of Eq. 8 from per-layer ghost norms,
            # at the logits of the forward it differentiates
            with layer("proxy_dp_grad"):
                g_prox, proxy_logits = dp_lib.dp_ghost_gradients(
                    self.apply_fn.layer_seam, proxy, x,
                    lambda z: self._proxy_logit_grads(z, private_logits, y),
                    key, clip=dpc.clip_norm, sigma=runtime_sigma(self.sigma),
                    block=dpc.per_example_chunk)
        else:
            proxy_logits = self.apply_fn(proxy, x)

        # private model: clean gradient of Eq. 9
        def private_obj(theta):
            lg = self.apply_fn(theta, x)
            return distill.private_loss(lg, proxy_logits, y, p4c.beta,
                                        p4c.distill_temperature)
        with layer("private_grad"):
            g_priv = jax.grad(private_obj)(private)

        # proxy model: DP gradient of Eq. 8 (the ghost route's is above)
        def proxy_obj(w, batch):
            lg = self.apply_fn(w, batch["x"])
            tgt = self.apply_fn(jax.lax.stop_gradient(private), batch["x"])
            return distill.proxy_loss(lg, tgt, batch["y"], p4c.alpha,
                                      p4c.distill_temperature)
        if not ghost:
            with layer("proxy_dp_grad"):
                if dpc.enabled:
                    g_prox = dp_lib.dp_gradients(
                        proxy_obj, proxy, {"x": x, "y": y}, key,
                        clip=dpc.clip_norm, sigma=runtime_sigma(self.sigma),
                        microbatches=dpc.microbatches,
                        per_example_chunk=dpc.per_example_chunk,
                        kernels=self.cfg.kernels)
                else:
                    g_prox = jax.grad(
                        lambda w: proxy_obj(w, {"x": x, "y": y}))(proxy)

        new_private = jax.tree_util.tree_map(lambda p, g: p - lr * g, private, g_priv)
        new_proxy = jax.tree_util.tree_map(lambda p, g: p - lr * g, proxy, g_prox)
        return (new_private, new_proxy,
                self._losses(private_logits, proxy_logits, y))

    def _affine_step(self, private, proxy, x, y, key, lr):
        """``_client_step`` for affine logits z = x·w + b under per-example
        DP, with four passes over the client batch x: one forward of both
        models (``_both_logits``); the private gradient in closed form,
        (Σ dl_priv, xᵀ dl_priv), from its logit gradient dl_priv, as one f32
        contraction at HIGHEST precision; the proxy's ‖x‖² and clipped
        contraction (``dp_lib.dp_affine_flat``). Exact for an affine model.
        The proxy is updated in the flat [b, w.ravel()] layout of its noise,
        the same element-wise p - lr·g; in that layout the chunk's memory
        peak is no higher than the per-tree update's."""
        p4c, dpc = self.cfg.p4, self.cfg.dp
        dp_lib.DP_PATH["affine_stacked"] += 1
        temp = p4c.distill_temperature
        private_logits, proxy_logits = self._both_logits(private, proxy, x)

        with layer("private_grad"):
            dl_priv = jax.grad(distill.private_loss)(
                private_logits, proxy_logits, y, p4c.beta, temp)
            g_priv = {"b": jnp.sum(dl_priv, axis=0),
                      "w": jnp.einsum("bf,bc->fc", x.astype(jnp.float32),
                                      dl_priv,
                                      precision=jax.lax.Precision.HIGHEST)}

        with layer("proxy_dp_grad"):
            dl_prox = self._proxy_logit_grads(proxy_logits, private_logits, y)
            g_prox = dp_lib.dp_affine_flat(x, dl_prox, key,
                                           clip=dpc.clip_norm,
                                           sigma=runtime_sigma(self.sigma))

        new_private = jax.tree_util.tree_map(lambda p, g: p - lr * g, private, g_priv)
        new_proxy = tree_unflatten_concat(
            tree_flatten_concat(proxy) - lr * g_prox, proxy)
        return (new_private, new_proxy,
                self._losses(private_logits, proxy_logits, y))

    def _in_blocks(self, f, x, y, zeros):
        """Σ over the batch's blocks of ``per_example_chunk`` sequences of
        ``f(xb, yb)``, each block's value weighted by its share c/n of the
        batch (a batch mean from block means); ``zeros`` is the sum's zero
        tree. One block where the chunk is 0 or holds the batch."""
        n = x.shape[0]
        c = self.cfg.dp.per_example_chunk or n
        if c >= n:
            return f(x, y)
        assert n % c == 0, (n, c)
        xs, ys = (t.reshape((n // c, c) + t.shape[1:]) for t in (x, y))

        def add(acc, xy):
            return jax.tree_util.tree_map(
                lambda a, v: a + v * (c / n), acc, f(*xy)), None
        return jax.lax.scan(add, zeros, (xs, ys))[0]

    def _lm_step(self, private, proxy, x, y, key, lr):
        """``_client_step`` for a language model on token rows x (n, s + 1)
        (``token_rows``), the labels of each row's first s tokens its next
        ones, in blocks of ``per_example_chunk`` sequences. Each block runs one forward of each model: the private
        model's gradient of Eq. 9 is its ``vjp`` with the block's logit
        gradient (scope ``private_grad``), and the proxy's clipped sum of
        Eq. 8 comes from ``dp.dp_layer_clipped_sum`` at its own logits
        (scope ``proxy_dp_grad``). The noise is drawn once for the batch,
        the flat draw of the other routes added leaf by leaf
        (``dp.add_noise_by_leaf``), so no flat copy of the model is made."""
        p4c, dpc = self.cfg.p4, self.cfg.dp
        x = self._tokens(x)
        n = x.shape[0]
        c = min(dpc.per_example_chunk or n, n)
        seam = self.apply_fn.layer_forward

        def block(t, _):
            xb, yb = t[:, :-1], t[:, 1:]
            with layer("private_grad"):
                pr_logits, pr_vjp = jax.vjp(
                    lambda p: self.apply_fn(p, xb), private)
            with layer("proxy_dp_grad"):
                g_px, px_logits = dp_lib.dp_layer_clipped_sum(
                    seam, proxy, xb,
                    lambda z: self._proxy_logit_grads(z, pr_logits, yb),
                    clip=dpc.clip_norm, denom=float(c))
            with layer("private_grad"):
                dl = jax.grad(distill.private_loss)(
                    pr_logits, px_logits, yb, p4c.beta,
                    p4c.distill_temperature)
                # the private backward after the proxy's: XLA would
                # otherwise run the two as one loop, with both backwards'
                # temporaries live at once
                dl, g_px = jax.lax.optimization_barrier((dl, g_px))
                g_pr, = pr_vjp(dl)
            return g_pr, g_px

        zeros = jax.tree_util.tree_map(jnp.zeros_like, (private, proxy))
        g_pr, g_px = self._in_blocks(block, x, y, zeros)
        with layer("proxy_dp_grad"), layer("dp_noise"):
            g_px = dp_lib.add_noise_by_leaf(g_px, key, runtime_sigma(self.sigma),
                                            dpc.clip_norm, float(n))
        new_private = jax.tree_util.tree_map(lambda p, g: p - lr * g, private, g_pr)
        new_proxy = jax.tree_util.tree_map(lambda p, g: p - lr * g, proxy, g_px)
        return new_private, new_proxy, None

    def _lm_losses(self, private, proxy, x, y):
        """The round's metrics (Eqs. 9 and 8) of a language model, in blocks
        of ``per_example_chunk`` sequences."""
        def losses(t, _):
            xb, yb = t[:, :-1], t[:, 1:]
            return self._losses(self.apply_fn(private, xb),
                                self.apply_fn(proxy, xb), yb)
        zeros = {"private_loss": jnp.zeros((), jnp.float32),
                 "proxy_loss": jnp.zeros((), jnp.float32)}
        return self._in_blocks(losses, self._tokens(x), y, zeros)

    def _tokens(self, x):
        """A language model's int32 token rows (b, s + 1) of data rows x."""
        return x if self.token_rows is None else self.token_rows(x)

    # ------------------------------------------------------------------
    def _local_round_keyed(self, states, xs, ys, keys):
        """K local steps, one PRNG key per client row (the seam the sharded
        engine drives with the global key split's shard slice). Returns
        per-client metric vectors: both losses at the updated models."""
        lr = self.cfg.train.learning_rate
        K = self.cfg.dp.local_steps
        affine = self._affine_route()
        lm = self.lm_cfg is not None

        def one_client(private, proxy, x, y, ckey):
            def body(carry, k):
                pr, px = carry
                pr, px, _ = self._client_step(pr, px, x, y,
                                              jax.random.fold_in(ckey, k), lr)
                return (pr, px), None
            (pr, px), _ = jax.lax.scan(body, (private, proxy), jnp.arange(K))
            with layer("metrics_step"):
                if lm:
                    metrics = self._lm_losses(pr, px, x, y)
                elif affine:
                    # one forward of both updated models
                    metrics = self._losses(*self._both_logits(pr, px, x), y)
                else:
                    # one more step at lr = 0 (XLA drops its unused
                    # gradients; the forwards remain)
                    _, _, metrics = self._client_step(
                        pr, px, x, y, jax.random.fold_in(ckey, K), 0.0)
            return pr, px, metrics

        args = (states["private"], states["proxy"], xs, ys, keys)
        if lm:
            priv, prox, metrics = jax.lax.map(lambda a: one_client(*a), args)
        else:
            priv, prox, metrics = jax.vmap(one_client)(*args)
        return {"private": priv, "proxy": prox}, metrics

    def _local_round_impl(self, states, xs, ys, key):
        """K local steps for all clients. xs: (M, B, feat), ys: (M, B).
        Unjitted body — traced either by the jitted ``local_round`` below or
        inside the engine's scanned round loop."""
        M = ys.shape[0]
        return self._local_round_keyed(states, xs, ys,
                                       jax.random.split(key, M))

    @functools.partial(jax.jit, static_argnums=0)
    def local_round(self, states, xs, ys, key):
        return self._local_round_impl(states, xs, ys, key)

    # ------------------------------------------------------------------
    def form_groups(self, states, seed: int = 0,
                    topology=None) -> List[List[int]]:
        """Phase-1 grouping. ``topology`` (optional) restricts each client's
        peer sampling to its communication-graph neighborhood — clients only
        measure similarity against peers they can reach (union adjacency for
        time-varying graphs)."""
        p4c = self.cfg.p4
        M = jax.tree_util.tree_leaves(states["proxy"])[0].shape[0]
        if p4c.similarity == "random":
            return random_groups(M, p4c.group_size, seed)
        with span("p4.form_groups"):
            with span("p4.l1_distance"):
                weights = flatten_clients(states["proxy"])
                dist = np.asarray(pairwise_l1(weights,
                                              kernels=self.cfg.kernels))
            nbhd = None
            if topology is not None:
                nbhd = (topology.union_adjacency()
                        if hasattr(topology, "union_adjacency")
                        else topology.adjacency)
            with span("p4.greedy"):
                return greedy_group_formation(dist, p4c.group_size,
                                              p4c.sample_peers, seed,
                                              neighborhoods=nbhd)

    # ------------------------------------------------------------------
    def evaluate(self, states, xs, ys):
        """Per-client test accuracy of the PERSONALIZED (private) model:
        the ``p4.evaluate`` span around its dispatch."""
        with span("p4.evaluate"):
            return _evaluate(self, states, xs, ys)

    # ------------------------------------------------------------------
    def fit(self, train_x, train_y, test_x, test_y, *, rounds: Optional[int] = None,
            key=None, eval_every: int = 20, batch_size: Optional[int] = None,
            groups: Optional[List[List[int]]] = None, seed: int = 0,
            bootstrap_rounds: int = 4, network=None, checkpoint_dir=None,
            resume: bool = False, target_epsilon: Optional[float] = None,
            mesh=None):
        """Full P4 on the federation engine: a full-batch bootstrap phase
        (no aggregation, no eval), host-side grouping on the DP weights, then
        the co-training phase as one scan-chunked engine run.

        bootstrap_rounds > 1 trades a few pre-grouping rounds for grouping
        SNR: DP noise on the weights grows √k while the data-driven weight
        divergence grows k, so the ℓ1 metric's signal-to-noise improves √k
        (EXPERIMENTS.md §Paper-validation discusses the feasibility envelope
        n·√k the paper's own setup implicitly satisfies with R=200–300).

        ``network`` (a P2PNetwork) and ``checkpoint_dir`` are forwarded to the
        engine as hooks: §4.5 byte accounting and save/resume come from the
        same loop as training.

        The co-train phase runs under ``cfg.schedule``: its RoundSchedule
        (full / client-sampling / async) and, when DP is on and
        ``cfg.schedule.accountant == "rdp"``, a PrivacyLedger whose cumulative
        (ε, δ) is recorded in ``history.metrics`` at every eval round —
        bootstrap rounds are accounted at q = 1 (full batch, full
        participation). ``target_epsilon`` calibrates σ against the ledger for
        the whole run instead of using Eq. 12's σ.

        ``mesh`` (a mesh with a ``clients`` axis, e.g. ``make_client_mesh()``)
        runs BOTH phases on the ShardedEngine: state/data stacks sharded over
        the client axis, group aggregation as collectives (shard-resident
        groups aggregate without any gather — the small-scale twin of
        ``make_p4_lm_step``'s pod-manual layout). Histories are bit-identical
        to the single-device engine (tests/test_sharded_engine.py)."""
        rounds = rounds or self.cfg.dp.rounds
        key = key if key is not None else jax.random.PRNGKey(self.cfg.train.seed)
        M, R = train_y.shape
        bs = batch_size or max(8, int(self.cfg.dp.sample_rate * R))
        data = FederatedData(train_x, train_y, test_x, test_y)
        strategy = P4Strategy(trainer=self)
        nb = max(1, bootstrap_rounds)
        dpc = self.cfg.dp

        schedule = make_schedule(self.cfg.schedule)
        ledger = None
        if dpc.enabled and self.cfg.schedule.accountant == "rdp":
            ledger = PrivacyLedger(sigma=self.sigma, delta=dpc.delta or 1.0 / R,
                                   sample_rate=bs / R,
                                   client_rate=schedule.client_fraction(M),
                                   local_steps=dpc.local_steps)
            if target_epsilon is not None:
                # σ must be live before the bootstrap traces (the strategy
                # closes over trainer.sigma); the bootstrap segment runs at
                # q = 1, so calibrate over both segments
                self.sigma = ledger.calibrate_segments(
                    target_epsilon, [(nb, 1.0), (rounds - nb, None)])
        elif target_epsilon is not None:
            raise ValueError("target_epsilon needs dp.enabled and "
                             "schedule.accountant='rdp'")

        def make_engine(**kw):
            if mesh is not None:
                return ShardedEngine(strategy, mesh=mesh, **kw)
            return Engine(strategy, **kw)

        # bootstrap local steps on the FULL local dataset (paper §3.3: weights
        # after first local training; Eq. 11's noise scales with 1/n, so the
        # full batch + k rounds maximize the grouping signal-to-noise)
        bootstrap = make_engine(eval_every=eval_every)
        states, _ = bootstrap.fit(data, rounds=nb, key=jax.random.fold_in(key, 0),
                                  batch_size=None, evaluate=False)
        if ledger is not None:
            ledger.advance(nb, q=1.0)   # full batch, full participation
        # topology-aware formation: when the run has an explicit graph that
        # exists BEFORE grouping (any family but "group", which is derived
        # from the groups themselves), Phase-1 peer sampling is restricted
        # to graph neighborhoods — clients can only measure peers they reach
        topo_cfg = getattr(self.cfg, "topology", None)
        pre_topo = None
        if topo_cfg is not None and topo_cfg.family not in ("none", "group"):
            from repro.topology import make_topology
            pre_topo = make_topology(topo_cfg, M)
        if groups is None:
            groups = self.form_groups(states, seed, topology=pre_topo)
        strategy.set_groups(groups, M)
        if topo_cfg is not None and topo_cfg.family != "none":
            if pre_topo is not None:
                strategy.set_topology(pre_topo)
            else:
                from repro.topology import make_topology
                strategy.set_topology(make_topology(topo_cfg, M,
                                                    groups=groups))

        # cfg.faults drives the co-train phase only: the bootstrap is the
        # grouping signal, and a faulted bootstrap would conflate grouping
        # noise with the resilience behavior under study
        from repro.resilience import make_fault_process
        faults = make_fault_process(self.cfg.faults, M) \
            if getattr(self.cfg, "faults", None) is not None else None
        engine = make_engine(eval_every=eval_every, network=network,
                             checkpoint_dir=checkpoint_dir, schedule=schedule,
                             ledger=ledger, faults=faults)
        states, history = engine.fit(data, rounds=rounds,
                                     key=jax.random.fold_in(key, 1),
                                     batch_size=bs, start_round=nb,
                                     state=states, resume=resume)
        return states, groups, history


@functools.partial(jax.jit, static_argnums=0)
def _evaluate(trainer: P4Trainer, states, xs, ys):
    if trainer.lm_cfg is not None:
        # a language model: the share of test tokens whose next token is
        # the argmax, client by client in blocks of sequences
        def lm_one(a):
            private, x, y = a

            def hits(t, _):
                pred = jnp.argmax(trainer.apply_fn(private, t[:, :-1]), -1)
                return jnp.mean((pred == t[:, 1:]).astype(jnp.float32))
            return trainer._in_blocks(hits, trainer._tokens(x), y,
                                      jnp.zeros((), jnp.float32))
        return jax.lax.map(lm_one, (states["private"], xs, ys))

    def one(private, x, y):
        return accuracy(trainer.apply_fn(private, x), y)
    return jax.vmap(one)(states["private"], xs, ys)


# ---------------------------------------------------------------------------
# Engine strategy: P4's co-training round as init/local_update/aggregate hooks
# ---------------------------------------------------------------------------

@register_strategy("p4")
@dataclass(eq=False)
class P4Strategy(Strategy):
    """P4 as an engine Strategy. Grouping is set between the bootstrap and
    co-training phases via ``set_groups`` (host-side — the greedy procedure
    is inherently sequential); until then ``aggregate`` is the identity."""
    trainer: P4Trainer = None
    groups: Optional[List[List[int]]] = None
    ids: Optional[jnp.ndarray] = None
    num_groups: int = 0

    @property
    def apply_fn(self):
        return self.trainer.apply_fn

    def set_groups(self, groups: List[List[int]], M: int) -> None:
        self.groups = groups
        self.ids = jnp.asarray(group_ids(groups, M))
        self.num_groups = len(groups)
        # padded member table for the in-jit rotating-aggregator lookup the
        # topology fault masks need: members[g, (r // rotation) % size_g]
        tmax = max(len(g) for g in groups)
        members = np.zeros((len(groups), tmax), np.int32)
        sizes = np.zeros((len(groups),), np.int32)
        for gi, g in enumerate(groups):
            members[gi, : len(g)] = g
            sizes[gi] = len(g)
        self._group_members = jnp.asarray(members)
        self._group_sizes = jnp.asarray(sizes)
        self.failover_count = 0  # rounds a group ran on a stand-in aggregator
        self.cache_token += 1    # aggregate() changed: invalidate engine chunks

    # ------------------------------------------------------------- topology
    def set_topology(self, topology) -> None:
        """Install the physical communication graph: group messages route
        along its shortest paths (per-link byte/hop accounting) and, with
        fault rates, member↔aggregator exchanges drop in-jit per round."""
        super().set_topology(topology)
        self._routing = None
        if topology is not None:
            from repro.topology.accounting import shortest_hops
            adj = (topology.union_adjacency()
                   if hasattr(topology, "topologies") else topology.adjacency)
            self._routing = shortest_hops(adj)

    def _has_faults(self) -> bool:
        t = self.topology
        return t is not None and (t.drop_prob > 0 or t.churn_prob > 0)

    def _aggregator_ids(self, r):
        """(M,) aggregator id per client at round r (traced) — the in-jit
        twin of ``p2p.aggregator_for_round`` over each client's own group."""
        rotation = max(self.trainer.cfg.p4.aggregator_rotation, 1)
        idx = (r // rotation) % self._group_sizes
        agg_per_group = self._group_members[
            jnp.arange(self.num_groups), idx]
        return agg_per_group[self.ids]

    def _fault_mask(self, r, key):
        """(M,) float32: 1 iff the client can reach this round's group
        aggregator — both endpoints up and the link alive. A churned
        aggregator takes its whole group's round down (every member masks to
        0, so the group mean leaves everyone untouched)."""
        from repro.topology.faults import draw_fault_masks
        M = self.ids.shape[0]
        t = self.topology
        keep, up = draw_fault_masks(key, M, t.drop_prob, t.churn_prob)
        agg = self._aggregator_ids(r)
        rows = jnp.arange(M)
        return jnp.where(rows == agg, up, keep[rows, agg])

    def _process_fault_mask(self, r, af):
        """(M,) reach mask under a correlated fault realization — with
        DETERMINISTIC FAILOVER: when the scheduled rotating aggregator is
        down, the next-up member (in rotation order) takes over; a group
        whose up-fraction is below the model's quorum — or with no member up
        at all — falls back to local-only for the round (mask 0 everywhere,
        so the masked group mean leaves every member untouched)."""
        real, quorum = af.real, af.model.quorum
        M = self.ids.shape[0]
        rotation = max(self.trainer.cfg.p4.aggregator_rotation, 1)
        members, sizes = self._group_members, self._group_sizes
        G, tmax = members.shape
        size = jnp.maximum(sizes, 1)
        idx = (r // rotation) % size                         # scheduled slot
        js = jnp.arange(tmax)
        cand_slot = (idx[:, None] + js[None, :]) % size[:, None]
        cand = members[jnp.arange(G)[:, None], cand_slot]    # (G, tmax)
        valid = (js[None, :] < sizes[:, None]).astype(jnp.float32)
        cand_up = real.up[cand] * valid
        first = jnp.argmax(cand_up, axis=1)      # first up in rotation order
        has_up = jnp.max(cand_up, axis=1)
        agg_g = cand[jnp.arange(G), first]
        up_counts = jax.ops.segment_sum(real.up, self.ids, self.num_groups)
        frac_up = up_counts / size.astype(jnp.float32)
        group_ok = ((has_up > 0) & (frac_up >= quorum)).astype(jnp.float32)
        agg = agg_g[self.ids]
        rows = jnp.arange(M)
        reach = jnp.where(rows == agg, real.up[rows], real.keep[rows, agg])
        return reach * group_ok[self.ids]

    def _context_fault_mask(self, r):
        """The correlated-process reach mask when the engine has a fault
        process installed (trace-time context), else None. Supersedes the
        topology's i.i.d. rates."""
        from repro.resilience import current_faults
        af = current_faults()
        if af is None or self.ids is None:
            return None
        return self._process_fault_mask(r, af)

    def init(self, key, data: FederatedData, batch_size):
        return self.trainer.init_clients(key, data.num_clients)

    def local_update(self, states, xs, ys, r, key):
        states, metrics = self.trainer._local_round_impl(states, xs, ys, key)
        return states, {k: jnp.mean(v) for k, v in metrics.items()}

    def local_update_keyed(self, states, xs, ys, r, keys):
        return self.trainer._local_round_keyed(states, xs, ys, keys)

    def aggregate(self, states, r, key):
        if self.ids is None:          # bootstrap phase: no groups yet
            return states
        cfm = self._context_fault_mask(r)
        if cfm is not None:
            return {"private": states["private"],
                    "proxy": masked_group_mean(states["proxy"], self.ids,
                                               self.num_groups, cfm)}
        if self._has_faults():
            # fault-injected round: only members whose link to this round's
            # aggregator survived exchange proxies (same masked-mean math as
            # partial participation — a dropped member keeps its own proxy)
            fm = self._fault_mask(r, key)
            return {"private": states["private"],
                    "proxy": masked_group_mean(states["proxy"], self.ids,
                                               self.num_groups, fm)}
        return {"private": states["private"],
                "proxy": group_mean(states["proxy"], self.ids, self.num_groups)}

    def aggregate_masked(self, states, r, key, mask):
        """Partial participation: the group mean runs over the round's cohort
        only — absent members' proxies are neither read nor overwritten.
        Link faults compose multiplicatively with the cohort mask."""
        if self.ids is None:
            return states
        cfm = self._context_fault_mask(r)
        if cfm is not None:
            mask = mask * cfm
        elif self._has_faults():
            mask = mask * self._fault_mask(r, key)
        return {"private": states["private"],
                "proxy": masked_group_mean(states["proxy"], self.ids,
                                           self.num_groups, mask)}

    # ------------------------------------------------------- sharded engine
    def _groups_shard_resident(self, ctx) -> bool:
        """Host-side layout check: True iff every group's members live on one
        mesh slice — the paper's "communicate only within your group" becomes
        structural and aggregation needs NO collective at all (the
        small-scale twin of make_p4_lm_step's pod-manual layout)."""
        if self.groups is None:
            return False
        return all(len({i // ctx.m for i in g}) == 1 for g in self.groups)

    def _local_ids(self, ctx):
        """This shard's group ids; padded slots get the out-of-range id
        ``num_groups`` so segment sums drop them."""
        padded = np.full((ctx.M_pad,), self.num_groups, np.int32)
        padded[: ctx.M] = np.asarray(self.ids)
        return ctx.rows(jnp.asarray(padded))

    def sharded_aggregate(self, states, r, key, ctx):
        if self.ids is None:
            return states
        if self._groups_shard_resident(ctx):
            # group-local layout: members and their mean never leave the
            # slice. masked_group_mean with the validity mask reproduces
            # group_mean's arithmetic bit-for-bit for real rows (counts are
            # identical, x·1.0 is exact) while padded rows keep their value.
            # Fault draws are replicated (same key on every slice), so the
            # sliced fault mask realizes the identical topology everywhere.
            cfm = self._context_fault_mask(r)
            if cfm is not None:
                local = ctx.rows(cfm)
            elif self._has_faults():
                local = ctx.rows(self._fault_mask(r, key))
            else:
                local = ctx.valid_mask()
            return {"private": states["private"],
                    "proxy": masked_group_mean(states["proxy"],
                                               self._local_ids(ctx),
                                               self.num_groups, local)}
        full = ctx.gather(states)
        return ctx.scatter_like(self.aggregate(full, r, key), full)

    def sharded_aggregate_masked(self, states, r, key, ctx, mask, local_mask):
        if self.ids is None:
            return states
        if self._groups_shard_resident(ctx):
            # local_mask is already zero on padded slots
            local = local_mask
            cfm = self._context_fault_mask(r)
            if cfm is not None:
                local = local * ctx.rows(cfm)
            elif self._has_faults():
                local = local * ctx.rows(self._fault_mask(r, key))
            return {"private": states["private"],
                    "proxy": masked_group_mean(states["proxy"],
                                               self._local_ids(ctx),
                                               self.num_groups, local)}
        full = ctx.gather(states)
        return ctx.scatter_like(self.aggregate_masked(full, r, key, mask),
                                full)

    def fingerprint(self):
        """Value-based chunk-cache key: only trace-relevant config enters, so
        an ε/σ sweep's points (which differ in dp.epsilon and the calibrated
        σ — both runtime) share compiled chunks whenever the formed groups
        coincide."""
        t, cfg = self.trainer, self.trainer.cfg
        groups = (None if self.groups is None
                  else tuple(tuple(g) for g in self.groups))
        topo = None if self.topology is None else self.topology.fingerprint()
        return ("p4", self.cache_token, t.model, t.feat_dim, t.num_classes,
                t.cnn_shape, t.lm_cfg, t.token_rows, cfg.p4, cfg.kernels,
                cfg.train.learning_rate,
                cfg.dp.enabled, cfg.dp.clip_norm, cfg.dp.local_steps,
                cfg.dp.microbatches, cfg.dp.per_example_chunk,
                isinstance(t.sigma, (int, float)) and t.sigma > 0,
                groups, self.num_groups, topo)

    def runtime_params(self):
        sigma = self.trainer.sigma
        if isinstance(sigma, (int, float)) and sigma > 0:
            return {"sigma": float(sigma)}
        return {}

    def set_sigma(self, sigma: float) -> None:
        """Target-ε calibration lands on the trainer (its σ is what
        ``_client_step`` reads at trace time — as the engine's runtime value,
        so recalibration does NOT invalidate compiled chunks)."""
        self.trainer.sigma = float(sigma)

    def eval_params(self, states):
        """Per-client PERSONALIZED (private) model."""
        return states["private"]

    def _host_failover_plan(self, r: int, hf):
        """Numpy twin of ``_process_fault_mask``'s aggregator selection: per
        group ``(aggregator, ok, failed_over)`` for byte accounting and the
        fault sweep's failover counts."""
        rotation = max(self.trainer.cfg.p4.aggregator_rotation, 1)
        plan = []
        for g in self.groups:
            size = len(g)
            idx = (r // rotation) % size
            agg, failed_over = None, False
            for j in range(size):
                cand = g[(idx + j) % size]
                if hf.up[cand] > 0:
                    agg, failed_over = cand, j > 0
                    break
            frac_up = float(sum(hf.up[i] for i in g)) / size
            ok = agg is not None and frac_up >= hf.model.quorum
            plan.append((agg, ok, failed_over))
        return plan

    def log_communication(self, net, states, r: int, mask=None,
                          phase_key=None, faults=None) -> None:
        """§4.5 Phase-2 accounting: members → rotating aggregator → members,
        one per-client proxy payload per message (matches
        ``p2p.simulate_group_round`` for the same groups — tested). Under a
        sampling schedule only the round's cohort exchanges messages: an
        absent client contributes zero bytes, and a group with fewer than two
        present members has nothing to aggregate.

        With a topology installed, messages route over the physical graph's
        shortest paths (one ``Message`` per link traversal — per-link
        byte/hop accounting), the aggregator is this round's full-group
        rotation (the same one the traced fault mask addresses), and the
        round's fault realization — re-derived from ``phase_key`` — zeroes
        the dropped member↔aggregator exchanges.

        With a correlated fault process (``faults`` — the engine's replayed
        ``HostFaults``), the aggregator is the traced failover choice
        (next-up member in rotation order), below-quorum groups fall silent
        (local-only), and ``self.failover_count`` tallies rounds a group ran
        on a stand-in aggregator."""
        if not self.groups:
            return
        rotation = self.trainer.cfg.p4.aggregator_rotation
        if faults is not None:
            from repro.topology.accounting import send_routed
            dist, next_hop = (self._routing if getattr(self, "_routing", None)
                              else (None, None))
            from repro.resilience.processes import FAULT_STATS
            for g, (agg, ok, failed_over) in zip(
                    self.groups, self._host_failover_plan(r, faults)):
                if not ok:
                    FAULT_STATS["quorum_silent_rounds"] += 1
                    continue
                senders = [i for i in g
                           if i != agg and (mask is None or mask[i] > 0)
                           and faults.keep[i, agg] > 0]
                if not senders:
                    continue
                if failed_over:
                    self.failover_count = getattr(self, "failover_count",
                                                  0) + 1
                    FAULT_STATS["failover_rounds"] += 1
                payload = jax.tree_util.tree_map(lambda t: t[g[0]],
                                                 states["proxy"])
                for i in senders:
                    send_routed(net, i, agg, payload, "proxy_update", r,
                                dist, next_hop)
                for i in senders:
                    send_routed(net, agg, i, payload, "aggregated_model", r,
                                dist, next_hop)
            return
        if self.topology is None:
            from repro.core.p2p import simulate_group_round
            for g in self.groups:
                present = g if mask is None else [i for i in g if mask[i] > 0]
                if len(present) < 2:
                    continue
                payload = jax.tree_util.tree_map(lambda t: t[g[0]],
                                                 states["proxy"])
                simulate_group_round(net, present, payload, rnd=r,
                                     rotation=rotation)
            return
        from repro.core.p2p import aggregator_for_round
        from repro.topology.accounting import send_routed
        keep = up = None
        if self._has_faults() and phase_key is not None:
            from repro.topology.faults import host_fault_masks
            keep, up = host_fault_masks(phase_key, r, 2, self.ids.shape[0],
                                        self.topology.drop_prob,
                                        self.topology.churn_prob)
        dist, next_hop = self._routing
        for g in self.groups:
            agg = aggregator_for_round(g, r, rotation)
            if up is not None and up[agg] <= 0:
                continue                  # churned aggregator: group idles
            present = [i for i in g
                       if (mask is None or mask[i] > 0)
                       and (i == agg or keep is None or keep[i, agg] > 0)]
            if len(present) < 2 or agg not in present:
                continue
            payload = jax.tree_util.tree_map(lambda t: t[g[0]],
                                             states["proxy"])
            for i in present:
                if i != agg:
                    send_routed(net, i, agg, payload, "proxy_update", r,
                                dist, next_hop)
            for i in present:
                if i != agg:
                    send_routed(net, agg, i, payload, "aggregated_model", r,
                                dist, next_hop)


# ---------------------------------------------------------------------------
# LM-scale P4 step (dry-run / production form)
# ---------------------------------------------------------------------------

def make_p4_lm_step(api_private, api_proxy, train_cfg: TrainConfig,
                    dp_cfg: DPConfig, p4_cfg: P4Config):
    """One jitted co-training step over G client groups (leading dim).

    params = {"private": (G, ...), "proxy": (G, ...)}; batch tokens (G, b, s).
    The G axis is sharded over "pod"; vmap over G keeps every reduction
    group-internal. Proxy gradients are microbatch-clipped + noised (the
    LM-scale DP realization); private gradients are clean.
    """
    from repro.models import transformer
    from repro.models.layers import kl_divergence, softmax_cross_entropy
    from repro.optim import make_optimizer

    cfg_t, cfg_w = api_private.cfg, api_proxy.cfg
    opt = make_optimizer(train_cfg)
    sigma = dp_cfg.noise_multiplier or dp_lib.noble_sigma(
        dp_cfg.epsilon, dp_cfg.delta or 1e-5, sample_rate=dp_cfg.sample_rate,
        rounds=dp_cfg.rounds, local_steps=dp_cfg.local_steps)

    def _logits(params, cfg, batch):
        lg, aux, _ = transformer.forward(params, cfg, batch)
        return lg, aux

    def per_group(theta, w, opt_t, opt_w, batch, key):
        tokens = batch["tokens"]
        # targets for mutual distillation (constant w.r.t. the other model)
        theta_logits = jax.lax.stop_gradient(_logits(theta, cfg_t, batch)[0])
        w_logits = jax.lax.stop_gradient(_logits(w, cfg_w, batch)[0])

        def private_obj(p, b):
            lg, aux = _logits(p, cfg_t, b)
            ce = softmax_cross_entropy(lg[:, :-1], b["tokens"][:, 1:])
            kl = kl_divergence(lg, b["w_logits"])
            return (1 - p4_cfg.beta) * ce + p4_cfg.beta * kl + aux

        def proxy_obj(p, b):
            lg, aux = _logits(p, cfg_w, b)
            ce = softmax_cross_entropy(lg[:, :-1], b["tokens"][:, 1:])
            kl = kl_divergence(lg, b["theta_logits"])
            return (1 - p4_cfg.alpha) * ce + p4_cfg.alpha * kl + aux

        bt = dict(batch, w_logits=w_logits)
        bw = dict(batch, theta_logits=theta_logits)
        g_theta = jax.grad(private_obj)(theta, bt)
        g_w = dp_lib.dp_gradients(proxy_obj, w, bw, key, clip=dp_cfg.clip_norm,
                                  sigma=sigma,
                                  microbatches=max(dp_cfg.microbatches, 1))
        new_theta, new_opt_t = opt.update(g_theta, opt_t, theta)
        new_w, new_opt_w = opt.update(g_w, opt_w, w)
        loss = softmax_cross_entropy(theta_logits[:, :-1], tokens[:, 1:])
        return new_theta, new_w, new_opt_t, new_opt_w, loss

    def _vmapped(params, opt_states, batch, key):
        G = batch["tokens"].shape[0]
        keys = jax.random.split(key, G)
        new_theta, new_w, opt_t, opt_w, loss = jax.vmap(per_group)(
            params["private"], params["proxy"],
            opt_states["private"], opt_states["proxy"], batch, keys)
        return ({"private": new_theta, "proxy": new_w},
                {"private": opt_t, "proxy": opt_w}, loss)

    def step(params, opt_states, batch, key):
        """Groups stacked on dim 0. If a mesh with a ``pod`` axis is active,
        the group dim is made MANUAL via partial shard_map — group-locality
        becomes structural (no partitioner guessing; §Perf hillclimb 3:
        vmap-only lowering leaked ~13 GB/step of embedding-gather traffic
        across pods, shard_map removes it by construction)."""
        from jax.sharding import PartitionSpec as P
        from repro.sharding.rules import _CTX
        ctx = getattr(_CTX, "val", None)
        mesh = ctx[0] if ctx else None
        # NOTE: partial-manual shard_map over "pod" is the structurally right
        # tool but crashes this XLA version's SPMD partitioner (fatal check in
        # spmd_partitioner_util.cc) when nested auto axes remain — kept behind
        # a flag; the shipping fix is untied embeddings + unsharded gather
        # table (§Perf hillclimb 3, iter 3). The small-scale twin of this
        # layout is the ShardedEngine client mesh.
        if (p4_cfg.manual_pod and mesh is not None
                and "pod" in getattr(mesh, "axis_names", ())):
            pspec = lambda tree: jax.tree_util.tree_map(lambda _: P("pod"), tree)

            def body(p, o, b, k):
                new_p, new_o, loss = _vmapped(p, o, b, k)
                return new_p, new_o, jax.lax.pmean(jnp.mean(loss), "pod")

            new_params, new_opt, loss = jax.shard_map(
                body, mesh=mesh,
                in_specs=(pspec(params), pspec(opt_states), pspec(batch), P()),
                out_specs=(pspec(params), pspec(opt_states), P()),
                axis_names={"pod"}, check_vma=False,
            )(params, opt_states, batch, key)
            return new_params, new_opt, {"loss": loss}
        new_params, new_opt, loss = _vmapped(params, opt_states, batch, key)
        return new_params, new_opt, {"loss": jnp.mean(loss)}

    return step
