"""Multi-mesh federation: the scanned round loop sharded over a client axis.

``ShardedEngine`` runs each ``RoundSchedule``'s round body under ``shard_map``
over a 1-D ``clients`` mesh (``repro.launch.mesh.make_client_mesh``): the
(M, ...) state/data stacks are sharded so each mesh slice hosts a disjoint
client shard, local training is embarrassingly parallel across slices, and
aggregation happens through explicit collectives (all_gather / ppermute; the
specs come from ``repro.sharding.rules.client_specs``). This is the structure
Bellet et al.'s P2P learning and MAPL exploit: clients are independent
between gossip steps.

Equivalence contract (locked by ``tests/test_sharded_engine.py``): a sharded
run is BIT-IDENTICAL to the single-device engine under FullParticipation, and
numerically tight under ClientSampling/AsyncStaleness. Three mechanisms make
that possible:

  * layout-invariant randomness — ``jax.random.split(key, M)`` is not
    prefix-stable, so every shard recomputes the full M-way split (cheap,
    replicated) and slices its own block (``ClientShardCtx.client_keys``);
    batch-index draws are likewise drawn at full (M, B) and row-sliced;
  * gather-exact aggregation — the default ``Strategy.sharded_aggregate``
    all_gathers the client stacks and runs the single-device aggregate
    verbatim, so the arithmetic (and its float rounding) is identical;
    strategies override with cheaper collectives where the result provably
    matches (P4's shard-resident group mean, DP-DSGT's ppermute ring);
  * deterministic padding — when M % n_devices != 0 the stacks are padded to
    the next multiple; padded slots train on zeroed data, are excluded from
    every aggregate (out-of-range segment ids / zero masks), and are sliced
    away before evaluate/checkpoint/History, so they can never leak into
    results or byte accounting.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.engine.loop import CHUNK_STATS, Engine, _cache_get, _cache_put
from repro.engine.schedule import LayoutCtx, wrap_overlap
from repro.engine.strategy import FederatedData, runtime_params
from repro.obs.layers import layer
from repro.sharding.rules import CLIENT_AXIS, client_specs


def _pad_rows(arr, target: int):
    """Zero-pad the leading (client) axis to ``target`` rows."""
    arr = jnp.asarray(arr)
    if arr.shape[0] == target:
        return arr
    pad = [(0, target - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return jnp.pad(arr, pad)


def _pad_axis1(arr, target: int):
    """Zero-pad axis 1 (the client axis of a (rounds, M, ...) stack) to
    ``target`` — the same zero padding ``rows`` applies per round."""
    if arr.shape[1] == target:
        return arr
    pad = [(0, 0), (0, target - arr.shape[1])] + [(0, 0)] * (arr.ndim - 2)
    return jnp.pad(arr, pad)


class ClientShardCtx(LayoutCtx):
    """Trace-time view of the client mesh inside the shard_map region.

    ``M`` is the true client count, ``n`` the mesh-axis size, ``M_pad`` the
    padded stack height (next multiple of n), ``m = M_pad // n`` the rows
    this shard holds. All helpers are traced (called from the round body).
    """

    def __init__(self, mesh, axis: str, num_clients: int):
        self.mesh = mesh
        self.axis = axis
        self.M = int(num_clients)
        self.n = int(mesh.shape[axis])
        self.M_pad = -(-self.M // self.n) * self.n
        self.m = self.M_pad // self.n
        # per-round prefetched randomness (``prefetched``): this round's
        # (m, 2) key slice / (m, B) batch-index slice, already sharded.
        # Single-use — consumed by the first client_keys / batch draw of the
        # round, so a second call (if a strategy ever makes one) falls back
        # to the replicated recompute instead of silently reusing a stream.
        self._pf_keys = None
        self._pf_idx = None

    @contextlib.contextmanager
    def prefetched(self, keys, idx):
        """Trace-time context installed by the engine's scan body: the
        round's per-client key slice and batch-index slice were derived
        *outside* the scan (one vmapped draw for the whole chunk, sharded
        over the mesh) so the round body itself runs zero random ops."""
        self._pf_keys, self._pf_idx = keys, idx
        try:
            yield
        finally:
            self._pf_keys = self._pf_idx = None

    # ------------------------------------------------------------- indexing
    def shard_offset(self):
        """First global (padded) client row held by this shard."""
        return jax.lax.axis_index(self.axis) * self.m

    def rows(self, arr):
        """Slice this shard's rows from a replicated full-stack array
        ((M, ...) arrays are zero-padded to (M_pad, ...) first)."""
        if arr.shape[0] == self.M:
            arr = _pad_rows(arr, self.M_pad)
        return jax.lax.dynamic_slice_in_dim(arr, self.shard_offset(), self.m)

    def valid_mask(self):
        """(m,) float32: 1 for real clients, 0 for padded slots."""
        idx = self.shard_offset() + jnp.arange(self.m)
        return (idx < self.M).astype(jnp.float32)

    # ------------------------------------------------------------ randomness
    def client_keys(self, key):
        """This shard's per-client keys — the *global* M-way split's slice,
        so client i's stream is independent of the mesh layout (split is not
        prefix-stable; every shard recomputes the full split, replicated).
        When the engine prefetched this round's slice, consume it instead —
        bit-identical (same derivation, hoisted out of the scan body)."""
        if self._pf_keys is not None:
            out, self._pf_keys = self._pf_keys, None
            return out
        return self.rows(jax.random.split(key, self.M))

    def sample_batches(self, train_x, train_y, key, batch_size):
        """Sharded ``sample_client_batches``: the (M, B) index draw
        is replicated (identical to the single-device draw), then row-sliced
        onto this shard's data. ``batch_size=None`` = full local batch."""
        if batch_size is None:
            return train_x, train_y
        with layer("sample_batches"):
            if self._pf_idx is not None:
                idx, self._pf_idx = self._pf_idx, None
            else:
                R = train_y.shape[1]
                idx = jax.random.randint(key, (self.M, batch_size), 0, R)
                idx = self.rows(idx)
            xs = jnp.take_along_axis(
                train_x, idx.reshape(idx.shape + (1,) * (train_x.ndim - 2)),
                axis=1)
            ys = jnp.take_along_axis(train_y, idx, axis=1)
        return xs, ys

    # ----------------------------------------------------------- collectives
    def gather(self, tree):
        """all_gather every leaf's client axis back to the full, UNPADDED
        (M, ...) stack (replicated on every shard)."""
        def g(x):
            full = jax.lax.all_gather(x, self.axis, axis=0, tiled=True)
            return full[: self.M] if self.M_pad != self.M else full
        return jax.tree_util.tree_map(g, tree)

    def scatter_like(self, out, full_in):
        """Re-shard an aggregate's output: leaves still shaped like the
        gathered (M, ...) input take this shard's row block (padded slots
        zeroed); leaves whose shape changed (e.g. FedAvg's (M, ...) → global
        model) are replicated results and pass through."""
        out_leaves, out_def = jax.tree_util.tree_flatten(out)
        full_leaves, full_def = jax.tree_util.tree_flatten(full_in)
        if out_def != full_def:
            return out
        res = []
        for o, f in zip(out_leaves, full_leaves):
            if o.shape == f.shape and o.ndim >= 1 and o.shape[0] == self.M:
                res.append(self.rows(o))
            else:
                res.append(o)
        return jax.tree_util.tree_unflatten(out_def, res)

    def metric_means(self, per_client: Dict[str, Any]) -> Dict[str, Any]:
        """Global scalar means bit-identical to the single-device
        ``jnp.mean`` over the (M,) per-client metric vector: gather, unpad,
        then mean the exact same vector."""
        def mean(v):
            if getattr(v, "ndim", 0) >= 1 and v.shape[0] == self.m:
                return jnp.mean(self.gather(v))
            return v
        return {k: mean(v) for k, v in per_client.items()}

    # ------------------------------------------------------ strategy hooks
    def local_update(self, strategy, state, xs, ys, r, key):
        with layer("local_update"):
            return strategy.sharded_local_update(state, xs, ys, r, key, self)

    def aggregate(self, strategy, state, r, key):
        with layer("aggregate"):
            return strategy.sharded_aggregate(state, r, key, self)

    def aggregate_masked(self, strategy, state, r, key, mask, rows):
        with layer("aggregate"):
            return strategy.sharded_aggregate_masked(state, r, key, self,
                                                     mask, rows)

    def merge_when(self, pred, merge, state):
        """Collectives must execute uniformly across shards, so the merge
        is select-based rather than ``lax.cond``: the aggregate (and its
        all_gather/psum) runs every round and the other rounds select the
        untouched state — bit-identical outcomes, uniform comms."""
        merged = merge(state)
        return jax.tree_util.tree_map(lambda m, s: jnp.where(pred, m, s),
                                      merged, state)

    def _mix_step(self, tree, plan, r, key, keep, halo):
        """ppermute halo exchange for banded/bounded-bandwidth graphs,
        slice-local gathers when every edge is shard-resident,
        gather→mix→re-shard otherwise (``repro.topology.mixing``)."""
        from repro.topology.mixing import mix_stacked_sharded
        return mix_stacked_sharded(tree, plan, r, key, self, keep=keep,
                                   halo=halo)


@dataclass(eq=False)
class ShardedEngine(Engine):
    """Engine whose chunks run under shard_map over a client mesh axis.

    ``mesh`` defaults to a 1-D mesh over every host device
    (``make_client_mesh``); pass any mesh containing ``client_axis``. The
    loop structure (eval cadence, History, ledger, checkpoints, byte
    accounting) is inherited — only the chunk execution and the
    client-padding representation differ, so sharded and single-device runs
    share everything the equivalence tests compare.
    """

    mesh: Optional[Any] = None
    client_axis: str = CLIENT_AXIS

    # the shard_map trace stays tap-free (callbacks inside the region would
    # fire once per device); the telemetry tap streams the same per-round
    # events host-side from the chunk's stacked outputs instead, so tap
    # on/off never changes the sharded trace or its cache key
    _tap_in_jit = False

    def __post_init__(self):
        super().__post_init__()
        if self.mesh is None:
            from repro.launch.mesh import make_client_mesh
            self.mesh = make_client_mesh(axis=self.client_axis)
        if self.client_axis not in self.mesh.shape:
            raise ValueError(
                f"mesh {dict(self.mesh.shape)} has no {self.client_axis!r} "
                "axis")
        self._padded_data: Dict[int, Tuple[Any, Any]] = {}
        self._M: Optional[int] = None

    # ------------------------------------------------------------ chunk key
    def _mesh_fingerprint(self) -> Tuple:
        n = int(self.mesh.shape[self.client_axis])
        devs = tuple(d.id for d in self.mesh.devices.flat)
        # self._M is set before any chunk builds (fit pads state first); it
        # keys the trace because ctx.M is baked into the compiled body
        return ("sharded", self.client_axis, n, devs, self._M)

    # --------------------------------------------------------- chunk builder
    def _chunk_fn(self, length: int, batch_size: Optional[int],
                  data: FederatedData):
        self._M = data.num_clients
        key_ = self._chunk_key(length, batch_size)
        fn = _cache_get(key_)
        if fn is not None:
            return fn
        ctx = ClientShardCtx(self.mesh, self.client_axis, data.num_clients)
        body = self.schedule.round_body(self.strategy, batch_size, ctx)
        body = wrap_overlap(body, self.strategy, ctx)
        faulted = self.faults is not None
        if faulted:
            from repro.resilience import wrap_round_body
            body = wrap_round_body(body, self.faults)
        mesh, axis = self.mesh, self.client_axis
        strategy = self.strategy
        stacked_state = self.strategy.state_client_stacked
        repl = lambda tree: jax.tree_util.tree_map(lambda _: P(), tree)

        # Hot-loop randomness, hoisted: every shard would otherwise
        # recompute the full M-way key split and (M, B) batch-index draw
        # inside every scanned round (replicated, so it is pure overhead
        # that scales with M). Derive the whole chunk's worth in one
        # vmapped draw — bit-identical streams, since fold_in/split/randint
        # are elementwise over rounds — pad with the same zeros ctx.rows
        # would add, and feed each round its slice through the scan xs.
        # The draw runs in its OWN unsharded jit: under the mesh constraint
        # the SPMD partitioner replicates the whole threefry chain on every
        # device (measured ~4x the unsharded cost), so hash once and reshard
        # the small result with device_put instead.
        R = data.train_y.shape[1]
        row_sh = NamedSharding(mesh, P(None, axis))

        @jax.jit
        def draw(phase_key, start):
            rounds = start + jnp.arange(length)
            rks = jax.vmap(lambda r: jax.random.fold_in(phase_key, r))(rounds)
            keys_all = jax.vmap(
                lambda k: jax.random.split(jax.random.fold_in(k, 1),
                                           ctx.M))(rks)
            pf = [_pad_axis1(keys_all, ctx.M_pad)]
            if batch_size is not None:
                idx_all = jax.vmap(
                    lambda k: jax.random.randint(
                        jax.random.fold_in(k, 0), (ctx.M, batch_size), 0,
                        R))(rks)
                pf.append(_pad_axis1(idx_all, ctx.M_pad))
            return tuple(pf)

        def chunk(state, phase_key, train_x, train_y, start, rt, *pf):
            CHUNK_STATS["traces"] += 1
            # under faults the carry is (strategy state, FaultState); the
            # fault chains are replicated — every slice steps the identical
            # Markov transition from the replicated phase key, which is what
            # makes sharded ≡ single-device hold under every fault regime
            st = state[0] if faulted else state
            s0 = (client_specs(st, ctx.M_pad, axis)
                  if stacked_state(st) else repl(st))
            sspec = (s0, repl(state[1])) if faulted else s0
            rounds = start + jnp.arange(length)

            def sharded(state, phase_key, tx, ty, rounds, rt, *pf):
                with runtime_params(rt):
                    st0 = state[0] if faulted else state
                    h0 = strategy.sharded_prefetch(st0, ctx)
                    h0 = () if h0 is None else h0
                    carry = (((state[0], h0), state[1]) if faulted
                             else (state, h0))

                    def scan_body(c, xs_r):
                        r, keys_r = xs_r[0], xs_r[1]
                        idx_r = xs_r[2] if len(xs_r) > 2 else None
                        with ctx.prefetched(keys_r, idx_r):
                            return body(c, r, phase_key, tx, ty)

                    # long chunks amortize scan bookkeeping by unrolling:
                    # XLA fuses across consecutive rounds, which is where
                    # the remaining per-round dispatch overhead of the
                    # shard_map hot loop lives. Short chunks (the eval-dense
                    # equivalence runs) keep unroll=1 — their bodies are the
                    # heavy mixing ones and 8x the trace is real compile cost.
                    unroll = 8 if length >= 64 else 1
                    carry, out = jax.lax.scan(scan_body, carry,
                                              (rounds,) + pf, unroll=unroll)
                    if faulted:
                        (st, _h), fstate = carry
                        return (st, fstate), out
                    st, _h = carry
                    return st, out

            return jax.shard_map(
                sharded, mesh=mesh,
                in_specs=(sspec, P(), P(axis), P(axis), P(), P())
                + (P(None, axis),) * len(pf),
                out_specs=(sspec, P()), check_vma=False,
            )(state, phase_key, train_x, train_y, rounds, rt, *pf)

        jfn = jax.jit(chunk, donate_argnums=0)

        def run(state, phase_key, train_x, train_y, start, rt):
            pf = tuple(jax.device_put(a, row_sh)
                       for a in draw(phase_key, start))
            return jfn(state, phase_key, train_x, train_y, start, rt, *pf)

        _cache_put(key_, run)
        return run

    # --------------------------------------------- padded client representation
    def _train_arrays(self, data: FederatedData):
        # the cached entry holds the FederatedData itself: the identity check
        # can't be fooled by a recycled object id, and the reference keeps the
        # id stable for as long as the entry exists
        cached = self._padded_data.get(id(data))
        if cached is None or cached[0] is not data:
            n = int(self.mesh.shape[self.client_axis])
            M_pad = -(-data.num_clients // n) * n
            sh = NamedSharding(self.mesh, P(self.client_axis))
            cached = (data,
                      jax.device_put(_pad_rows(data.train_x, M_pad), sh),
                      jax.device_put(_pad_rows(data.train_y, M_pad), sh))
            self._padded_data[id(data)] = cached
        return cached[1], cached[2]

    def _prepare_state(self, state, data: FederatedData):
        self._M = M = data.num_clients
        n = int(self.mesh.shape[self.client_axis])
        M_pad = -(-M // n) * n
        stacked = self.strategy.state_client_stacked(state)
        row_sh = NamedSharding(self.mesh, P(self.client_axis))
        rep_sh = NamedSharding(self.mesh, P())

        def prep(leaf):
            leaf = jnp.asarray(leaf)
            if stacked and leaf.ndim >= 1 and leaf.shape[0] == M:
                return jax.device_put(_pad_rows(leaf, M_pad), row_sh)
            return jax.device_put(leaf, rep_sh)

        return jax.tree_util.tree_map(prep, state)

    def _finalize_state(self, state):
        M = self._M
        n = int(self.mesh.shape[self.client_axis])
        M_pad = -(-M // n) * n
        stacked = self.strategy.state_client_stacked(state)
        dev0 = jax.devices()[0]

        def unpad(leaf):
            if (stacked and getattr(leaf, "ndim", 0) >= 1
                    and leaf.shape[0] == M_pad and M_pad != M):
                leaf = leaf[:M]
            # devolve to a plain single-device array: evaluate/checkpoint/
            # callers then run the exact single-device computation (leaving
            # the mesh sharding in place reorders eval reductions by a ulp)
            return jax.device_put(leaf, dev0)

        return jax.tree_util.tree_map(unpad, state)
