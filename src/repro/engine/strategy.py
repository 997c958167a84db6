"""Strategy interface + registry for the unified federation engine.

Every federated method in the repo — P4 and all §4.2.1 baselines — is a
``Strategy``: a small object exposing ``init → local_update → aggregate →
eval_params`` hooks over client state pytrees (usually stacked ``(M, ...)``
trees, one leading slot per client). The engine (``repro.engine.loop``) owns
the round schedule, on-device batch sampling, eval cadence, history, and the
optional communication/checkpoint hooks, so methods cannot drift apart on
anything but their update rule.

This mirrors how Bellet et al. (Personalized and Private P2P ML) and MAPL
frame decentralized learning: one round schedule, pluggable local-update /
communicate / aggregate operators.

Registry: ``@register_strategy("name")`` on the class; ``get_strategy("name")``
returns the class so sweeps can be driven by config strings.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.engine.schedule import ResidentCtx

_REGISTRY: Dict[str, type] = {}


# ---------------------------------------------------------------------------
# Runtime parameters: host-side scalars (today: the DP noise multiplier σ)
# threaded into the jitted chunk as *arguments* instead of being baked into
# the trace as constants. This is what lets a sweep over ε/σ reuse one
# compiled chunk across points: the engine activates the context while the
# chunk traces, strategies read the traced value through ``runtime_sigma``,
# and subsequent calls just pass a different scalar.
# ---------------------------------------------------------------------------

_RUNTIME = threading.local()


@contextlib.contextmanager
def runtime_params(params: Dict[str, jnp.ndarray]):
    """Trace-time context installed by the engine around the chunk body."""
    prev = getattr(_RUNTIME, "params", None)
    _RUNTIME.params = params
    try:
        yield
    finally:
        _RUNTIME.params = prev


# Compute/communication overlap: the sharded engine's scan body threads the
# strategy's prefetched halo blocks (``Strategy.sharded_prefetch``) through
# the scan carry and exposes them to the NEXT round's hooks through this
# context — round r+1's boundary ppermute is issued at the end of round r's
# body, before the next local_update trains. Same trace-time mechanism as
# runtime_params: no hook-signature changes for strategies that don't opt in.
_HALOS = threading.local()


@contextlib.contextmanager
def sharded_halos(halos):
    """Trace-time context installed by the sharded scan body around each
    round: ``halos`` is whatever the strategy's ``sharded_prefetch`` returned
    at the end of the previous round (None when it doesn't prefetch)."""
    prev = getattr(_HALOS, "value", None)
    _HALOS.value = halos
    try:
        yield
    finally:
        _HALOS.value = prev


def current_halos():
    return getattr(_HALOS, "value", None)


def runtime_sigma(static_sigma):
    """The traced σ if an engine runtime context is active, else the host
    value. Only substitutes when DP is actually on (static σ > 0) so the
    σ == 0 trace keeps its noiseless structure — DP on/off is part of the
    chunk-cache key, the magnitude is not."""
    if isinstance(static_sigma, (int, float)) and static_sigma > 0:
        d = getattr(_RUNTIME, "params", None)
        if d is not None and "sigma" in d:
            return d["sigma"]
    return static_sigma


class _IdToken:
    """Identity-keyed fingerprint entry for field values that aren't
    hashable by value. The chunk cache holds the key (and therefore the
    object) alive, so the identity is stable for the cache's lifetime —
    two distinct instances never collide, they just don't share chunks."""
    __slots__ = ("obj",)

    def __init__(self, obj):
        self.obj = obj

    def __hash__(self):
        return id(self.obj)

    def __eq__(self, other):
        return isinstance(other, _IdToken) and other.obj is self.obj


def register_strategy(name: str) -> Callable[[type], type]:
    """Class decorator: register a Strategy subclass under ``name``."""
    def deco(cls: type) -> type:
        _REGISTRY[name] = cls
        cls.name = name
        return cls
    return deco


def get_strategy(name: str) -> type:
    if name not in _REGISTRY:
        raise KeyError(f"unknown strategy {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def available_strategies() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


@dataclass(eq=False)  # hashable by identity: safe to close over in jit
class FederatedData:
    """Client-stacked datasets, device-resident for the whole run.

    ``train_x: (M, R, ...)``, ``train_y: (M, R)``; test likewise (the test
    leading dim may differ from M, e.g. the pooled-data centralized baseline
    trains on (1, N, ...) but reports per-client test accuracy).
    """
    train_x: jnp.ndarray
    train_y: jnp.ndarray
    test_x: jnp.ndarray
    test_y: jnp.ndarray

    def __post_init__(self):
        self.train_x = jnp.asarray(self.train_x)
        self.train_y = jnp.asarray(self.train_y)
        self.test_x = jnp.asarray(self.test_x)
        self.test_y = jnp.asarray(self.test_y)

    @property
    def num_clients(self) -> int:
        return self.train_y.shape[0]

    @property
    def samples_per_client(self) -> int:
        return self.train_y.shape[1]


@dataclass(eq=False)
class Strategy:
    """Base class for federated methods run by the engine.

    State is an arbitrary pytree owned by the strategy (stacked client
    params, plus any method state: control variates, gradient trackers, ...).
    All hooks except ``init`` are traced into the engine's scanned round body,
    so they must be jit-compatible; the round index ``r`` and all keys arrive
    as traced scalars.
    """

    # plain class attribute (NOT a dataclass field): register_strategy
    # overrides it per subclass and instances resolve it through the class
    name = "base"
    # engine chunk-cache invalidation: the compiled round chunks close over
    # the strategy, so any host-side attribute change that alters the traced
    # computation (e.g. P4Strategy.set_groups) MUST bump this counter.
    # (σ is exempt: it flows through the chunk as a runtime argument.)
    cache_token = 0
    # communication topology (repro.topology): None = the strategy's built-in
    # pattern (DP-DSGT's ring, P4's group mean). Subclasses that shadow this
    # with a dataclass field get it hashed into the default fingerprint
    # automatically (Topology is hashable by value).
    topology = None
    _mix_plan = None

    # ------------------------------------------------------------ chunk cache
    def fingerprint(self) -> Tuple:
        """Value key for the engine's cross-instance compiled-chunk cache:
        two strategies with equal fingerprints must trace to the same chunk
        computation (σ excluded — it is a runtime argument; only its
        positivity, which gates the noise ops, is keyed). The default walks
        the dataclass fields; unhashable values fall back to identity tokens
        (safe: no cross-instance reuse). Override to enable value-based
        reuse for composite fields (see P4Strategy)."""
        vals = [type(self).__name__, self.cache_token]
        # the configured topology changes the traced mixing step even when it
        # is not a dataclass field (set via set_topology); include it so two
        # same-token instances with different graphs can never share a chunk
        field_names = {f.name for f in dataclasses.fields(self)}
        if "topology" not in field_names and self.topology is not None:
            vals.append(self.topology.fingerprint())
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if f.name == "sigma":
                vals.append(isinstance(v, (int, float)) and v > 0)
                continue
            try:
                hash(v)
            except TypeError:
                v = _IdToken(v)
            vals.append(v)
        return tuple(vals)

    def runtime_params(self) -> Dict[str, float]:
        """Host scalars the engine passes into the chunk each call (read back
        at trace time via ``runtime_sigma``). Keys must be stable for a given
        fingerprint — presence/absence is part of the chunk-cache key."""
        sigma = getattr(self, "sigma", 0.0)
        if isinstance(sigma, (int, float)) and sigma > 0:
            return {"sigma": float(sigma)}
        return {}

    # ------------------------------------------------------------------ hooks
    def init(self, key, data: FederatedData, batch_size: Optional[int]):
        """Build the initial state pytree (host-side, before tracing)."""
        raise NotImplementedError

    def local_update(self, state, xs, ys, r, key):
        """One round of local training on the sampled batches.

        Returns ``(state, metrics)`` where metrics is a (possibly empty) dict
        of scalars with a structure that is identical every round. Default:
        ``local_update_keyed`` with ``split(key, M)``, metrics meaned.
        """
        return self._keyed_update(ResidentCtx(ys.shape[0]), state, xs, ys, r,
                                  key)

    def local_update_keyed(self, state, xs, ys, r, keys):
        """Per-client-keyed form of ``local_update``: ``keys`` is the stacked
        key array aligned with the leading client axis, so a client shard or
        a paged cohort is driven with the *global* key split's rows —
        per-client randomness becomes layout-invariant. Returns
        ``(state, per_client_metrics)`` with (M',)-shaped metric leaves."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement local_update_keyed; "
            "it cannot run under the sharded engine")

    def _keyed_update(self, ctx, state, xs, ys, r, key):
        """``local_update_keyed`` on the layout's rows of the global key
        split, with the metric means the resident path records."""
        state, per_client = self.local_update_keyed(
            state, xs, ys, r, ctx.client_keys(key))
        return state, ctx.metric_means(per_client)

    def aggregate(self, state, r, key):
        """Communication/aggregation step after local updates (identity by
        default — e.g. the local-training baseline never communicates)."""
        return state

    # ------------------------------------------------------------- topology
    def set_topology(self, topology, kernels=None) -> None:
        """Install a communication graph (``repro.topology``): the mixing
        plan is compiled once host-side and the layout context's ``mix``
        applies it per round. Changes the traced computation, so compiled
        chunks are invalidated; ``None`` restores the strategy's built-in
        pattern. ``kernels`` (a ``KernelConfig``) opts the halo mix step
        into the dispatch autotuner's row-tile search."""
        from repro.topology.mixing import make_plan
        self.topology = topology
        self._mix_plan = (None if topology is None
                          else make_plan(topology, kernels=kernels))
        self.cache_token += 1

    # ------------------------------------------------------- sharded engine
    # These hooks run inside a shard_map region over the client mesh axis
    # (``repro.engine.sharded``): ``state``/``xs``/``ys`` hold this shard's
    # client rows, ``ctx`` is the ClientShardCtx. Defaults are bit-exact with
    # the single-device path by construction; strategies override to replace
    # the all_gather round-trip with cheaper collectives (P4's pod-resident
    # group mean, DP-DSGT's ppermute ring gossip).

    def state_client_stacked(self, state) -> bool:
        """Whether the *carry* state is client-stacked (leading client dim on
        every stacked leaf). Server-style strategies whose carry is a single
        global model (FedAvg, Scaffold) return False so the engine replicates
        the carry instead of trusting the leading-dim shape heuristic."""
        return True

    def sharded_local_update(self, state, xs, ys, r, key, ctx):
        """Local update on this shard's clients. Default: derive the full
        run's per-client keys (identical on every shard), feed this shard's
        slice to ``local_update_keyed``, and reduce metrics to the same
        global means the single-device path records."""
        return self._keyed_update(ctx, state, xs, ys, r, key)

    def sharded_prefetch(self, state, ctx):
        """Issue next-round boundary transfers from the end-of-round state
        (compute/communication overlap). Called by the sharded scan body
        right after the round's hooks; whatever pytree it returns is carried
        to the next round and exposed back to the hooks via
        ``current_halos()`` while they trace. Return None (the default) to
        opt out — the carry then holds an empty placeholder and the mixing
        step issues its own exchange inline."""
        return None

    def sharded_aggregate(self, state, r, key, ctx):
        """Aggregation as explicit collectives. Default: all_gather the
        client stacks to the full (M, ...) trees, run the single-device
        ``aggregate`` verbatim (bit-identical arithmetic), slice this shard's
        rows back out. Replicated (non-stacked) outputs pass through.
        Strategies that never communicate (``aggregate`` left as the base
        identity — local training, gossip-in-local_update methods) skip the
        round-trip entirely."""
        if type(self).aggregate is Strategy.aggregate:
            return state
        full = ctx.gather(state)
        return ctx.scatter_like(self.aggregate(full, r, key), full)

    def sharded_aggregate_masked(self, state, r, key, ctx, mask, local_mask):
        """Cohort aggregation under a sampling schedule: ``mask`` is the full
        (M,) participation mask (replicated — every shard drew the same one),
        ``local_mask`` its rows for this shard."""
        if (type(self).aggregate is Strategy.aggregate
                and type(self).aggregate_masked is Strategy.aggregate_masked):
            # merge_participation(state, identity(state)) == state bitwise
            return state
        full = ctx.gather(state)
        return ctx.scatter_like(self.aggregate_masked(full, r, key, mask),
                                full)

    # --------------------------------------------------------- paged cohorts
    # These hooks run inside a PagedEngine chunk (``repro.engine.population``):
    # ``state``/``xs``/``ys`` hold the cohort's compact (C, ...) rows and
    # ``pctx`` is the PagedCtx mapping cohort slots to global client ids.
    # Defaults are bit-exact with the resident path by construction: per-client
    # randomness comes from the *global* M-way key split (layout-invariant),
    # and cohort aggregation scatter-expands to the full (M, ...) stack so the
    # resident reduction runs verbatim (identical float rounding).

    def paged_local_update(self, state, xs, ys, r, key, pctx):
        """Local update on the cohort's rows. Default: slice the global
        per-client key split at the cohort's ids and reduce metrics over the
        valid (non-padding) slots."""
        return self._keyed_update(pctx, state, xs, ys, r, key)

    def paged_aggregate_masked(self, state, r, key, mask, pctx):
        """Cohort aggregation under a sampling schedule: ``mask`` is the full
        (M,) participation mask (the paged body draws the identical full-M
        mask the resident body draws). Default: scatter-expand the compact
        rows into a zeros-backed (M, ...) stack, run the resident
        ``aggregate_masked`` verbatim, take the cohort rows back. Absent
        clients contribute exact zero terms either way, so the reduction is
        bit-identical up to the sign of zero."""
        if (type(self).aggregate is Strategy.aggregate
                and type(self).aggregate_masked is Strategy.aggregate_masked):
            # merge_participation(state, identity(state)) == state bitwise
            return state
        full = pctx.expand(state)
        return pctx.compact_like(self.aggregate_masked(full, r, key, mask),
                                 full)

    def paged_cohort_closure(self, ids, rounds):
        """Host-side: global client ids a chunk must page in beyond the
        sampled participants — the union with every participant's in-neighbors
        under the configured mixing plan (a participant's gossip step reads
        its neighbors' last-known state). ``ids``/``rounds`` are numpy."""
        if self._mix_plan is None:
            return ids
        from repro.topology.mixing import plan_in_neighbors
        return plan_in_neighbors(self._mix_plan, ids, rounds)

    # ------------------------------------------------- partial participation
    def merge_participation(self, prev_state, new_state, mask):
        """Under a ClientSampling schedule: keep absent clients' state.

        Default: for every leaf stacked per-client (leading dim == M with an
        unchanged shape), select ``new`` where the (M,) mask is 1 and ``prev``
        where it is 0; other leaves (and states whose pytree structure changed
        mid-round, e.g. a server-style global→clients expansion) pass through
        for ``aggregate_masked`` to handle. Override when client identity
        lives elsewhere in the state."""
        prev_leaves, prev_def = jax.tree_util.tree_flatten(prev_state)
        new_leaves, new_def = jax.tree_util.tree_flatten(new_state)
        if prev_def != new_def:
            return new_state
        M = mask.shape[0]

        def sel(o, n):
            if n.ndim >= 1 and n.shape == o.shape and n.shape[0] == M:
                m = mask.reshape((M,) + (1,) * (n.ndim - 1))
                return jnp.where(m > 0, n, o)
            return n

        return jax.tree_util.tree_unflatten(
            prev_def, [sel(o, n) for o, n in zip(prev_leaves, new_leaves)])

    def aggregate_masked(self, state, r, key, mask):
        """Aggregation under partial participation. Default: run the full
        aggregate, then keep absent clients' pre-aggregation state — present
        clients therefore see absent peers' last-known values (a stale cache),
        and absent clients receive nothing. Override for cohort-weighted
        aggregation (FedAvg/Scaffold means, P4's masked group mean)."""
        return self.merge_participation(
            state, self.aggregate(state, r, key), mask)

    def eval_params(self, state):
        """Stacked (M_test, ...) per-client parameters to evaluate."""
        raise NotImplementedError

    # ---------------------------------------------------------------- derived
    def evaluate(self, state, test_x, test_y):
        """(M,) per-client test accuracy; override for non-stacked methods."""
        from repro.core.small_models import accuracy
        params = self.eval_params(state)
        return jax.vmap(lambda p, x, y: accuracy(self.apply_fn(p, x), y))(
            params, test_x, test_y)

    # ------------------------------------------------------- optional hooks
    def log_communication(self, net, state, r: int, mask=None,
                          phase_key=None, faults=None) -> None:
        """Record the round's messages on a P2PNetwork (host-side, called by
        the engine at eval boundaries for each elapsed round). ``mask`` is the
        round's (M,) participation mask under a sampling schedule (None for
        full participation) — absent clients must contribute zero bytes.
        ``phase_key`` is the engine's phase key: strategies with a faulty
        topology re-derive the round's exact link-fault realization from it
        (``repro.topology.faults.host_fault_masks``) so dropped links also
        contribute zero bytes. ``faults`` is the round's replayed
        ``repro.resilience.HostFaults`` when the engine runs a correlated
        fault process (it supersedes the topology's i.i.d. rates)."""

    def set_sigma(self, sigma: float) -> None:
        """Engine hook for target-ε calibration (``Engine.fit(target_epsilon=
        ...)``): install the calibrated noise multiplier. σ flows into
        compiled chunks as a runtime argument (``runtime_sigma``), so this no
        longer invalidates the chunk cache — which is exactly what lets an
        ε-sweep reuse one compiled chunk across calibration points."""
        if not hasattr(self, "sigma"):
            raise AttributeError(
                f"{type(self).__name__} has no 'sigma' attribute; override "
                "set_sigma to route the calibrated noise multiplier")
        self.sigma = float(sigma)

    def state_to_save(self, state):
        """Pytree persisted by the engine's checkpoint hook."""
        return state
