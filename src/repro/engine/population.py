"""Million-client federation: a host-resident population with paged cohorts.

The resident engine keeps every client in a device ``(M, ...)`` stack, so M
is capped by device memory. ``PagedEngine`` decouples the *population* from
the *cohort*: the full per-client state and training data live host-side in
NumPy (``VirtualPopulation`` / ``HostFederatedData``), and each scanned chunk
materializes only the clients it can possibly touch as a compact ``(C, ...)``
device stack — the union of the chunk's sampled cohorts (replayed host-side
from the same PRNG streams the device draws), closed over gossip in-neighbors
(``Strategy.paged_cohort_closure``). This is the APPFL-style rank-0
orchestrator shape (SNIPPETS.md §2) rebuilt device-native.

The paged ≡ resident contract (locked by ``tests/_sharded_equivalence_main``
and ``tests/test_population.py``):

  * absent clients are bit-frozen — they are either not paged in at all, or
    paged in as neighbors and their updates discarded by the same
    ``merge_participation`` selects the resident body runs;
  * per-client PRNG streams are layout-invariant — every per-client draw is
    made at full population size (the M-way key split, the (M, B) batch-index
    draw, the (M,) participation mask) and sliced at the cohort's *global*
    ids, never keyed by cohort slot;
  * cohort aggregation scatter-expands into a zeros-backed (M, ...) stack and
    runs the resident reduction verbatim, so the float rounding is identical;
  * the ``PrivacyLedger`` sees the same full-M participation masks and
    advances by the same round counts, so (ε, δ) rates are computed against
    the full population M.

Under ``FullParticipation`` / ``AsyncStaleness`` every client trains every
round, so the cohort is the whole population: the engine gathers the full
stacks and runs the resident chunk (``ResidentCtx``) verbatim (trivially
bit-exact). Only ``ClientSampling`` runs its body over the compact cohort
(``PagedCtx``).

Double-buffered prefetch: while a chunk executes on device (JAX dispatch is
asynchronous), a host thread plans and gathers the next chunk's cohort
(``CohortPrefetcher``). A prefetched state gather is validated against the
population's version counter at take time — a scatter in between re-gathers
instead of serving stale rows (property-tested).
"""
from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.loop import CHUNK_STATS, Engine, _cache_get, _cache_put
from repro.engine.schedule import ClientSampling, LayoutCtx
from repro.engine.strategy import FederatedData, runtime_params
from repro.obs.layers import layer
from repro.obs.probes import Probe

# process-wide twin of the per-instance ``CohortPrefetcher.stats`` dicts:
# ``misses`` are prefetch stalls (the chunk gathers synchronously), ``stale``
# are version-mismatch re-gathers — scoped per run via
# ``repro.obs.probe_deltas("engine.prefetch")``
PREFETCH_STATS = Probe("engine.prefetch", {"submitted": 0, "hits": 0,
                                           "misses": 0, "stale": 0})


@dataclass(eq=False)
class HostFederatedData:
    """NumPy twin of ``FederatedData``: client stacks that never leave the
    host. Duck-types the attributes strategies touch (``init``/``evaluate``
    coerce through jnp on use), so a PagedEngine run needs no strategy-side
    data changes."""

    train_x: np.ndarray
    train_y: np.ndarray
    test_x: np.ndarray
    test_y: np.ndarray

    def __post_init__(self):
        self.train_x = np.asarray(self.train_x)
        self.train_y = np.asarray(self.train_y)
        self.test_x = np.asarray(self.test_x)
        self.test_y = np.asarray(self.test_y)

    @property
    def num_clients(self) -> int:
        return self.train_y.shape[0]

    @property
    def samples_per_client(self) -> int:
        return self.train_y.shape[1]


def as_host_data(data) -> HostFederatedData:
    if isinstance(data, HostFederatedData):
        return data
    return HostFederatedData(np.asarray(data.train_x),
                             np.asarray(data.train_y),
                             np.asarray(data.test_x),
                             np.asarray(data.test_y))


class VirtualPopulation:
    """Host-resident store for the client-stacked state leaves.

    One NumPy array per stacked leaf (leading axis = global client id), a
    monotone ``version`` counter bumped by every scatter (the prefetcher's
    staleness check), and per-row dirty tracking since the last checkpoint
    save (``repro.checkpoint.save_population`` writes only dirty rows)."""

    def __init__(self, num_clients: int):
        self.M = int(num_clients)
        self.arrays: List[np.ndarray] = []
        self.version = 0
        self._dirty = np.zeros((self.M,), bool)
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self.arrays)

    def add(self, arr: np.ndarray) -> int:
        if arr.shape[0] != self.M:
            raise ValueError(f"leaf rows {arr.shape[0]} != M={self.M}")
        self.arrays.append(np.ascontiguousarray(arr))
        return len(self.arrays) - 1

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in self.arrays)

    # ------------------------------------------------------- gather / scatter
    def gather(self, rows: np.ndarray) -> List[np.ndarray]:
        """Copy the rows of every leaf (fancy indexing ⇒ fresh arrays)."""
        with self._lock:
            return [a[rows] for a in self.arrays]

    def scatter(self, rows: np.ndarray, leaves: List[np.ndarray]) -> None:
        """Write updated rows back and mark them dirty. Untouched rows are
        bit-unchanged by construction (they are simply not written)."""
        with self._lock:
            for a, v in zip(self.arrays, leaves):
                a[rows] = np.asarray(v, a.dtype)
            self._dirty[rows] = True
            self.version += 1

    # ----------------------------------------------------------- checkpoints
    def dirty_rows(self) -> np.ndarray:
        with self._lock:
            return np.nonzero(self._dirty)[0]

    def clear_dirty(self) -> None:
        with self._lock:
            self._dirty[:] = False

    def mark_all_dirty(self) -> None:
        with self._lock:
            self._dirty[:] = True


class _PopLeaf:
    """Pytree-leaf placeholder marking a state leaf that lives in the
    population store (identified by its flatten index)."""

    __slots__ = ("idx", "shape", "dtype")

    def __init__(self, idx: int, shape, dtype):
        self.idx, self.shape, self.dtype = idx, tuple(shape), dtype

    def __repr__(self):
        return f"_PopLeaf({self.idx}, {self.shape}, {self.dtype})"


class PagedCtx(LayoutCtx):
    """Trace-time view of the cohort boundary inside a paged chunk.

    ``M`` is the population size, ``C`` the padded cohort width. The chunk
    passes the cohort's global ids as a TRACED ``(C,)`` argument (padding
    slots carry the sentinel id ``M``), so one compiled chunk serves every
    cohort of the same padded width; ``installed`` is the trace-time context
    the chunk wraps around its scan (same mechanism as the sharded engine's
    ``ctx.prefetched``)."""

    def __init__(self, num_clients: int, cohort: int):
        self.M = int(num_clients)
        self.C = int(cohort)
        self.ids = None          # (C,) int32 global ids, M on padding slots
        self.ids_clip = None     # (C,) int32 clipped to [0, M) for gathers
        self.valid = None        # (C,) float32, 0 on padding slots
        self.slot_of = None      # (M + 1,) int32 global id -> cohort slot

    def installed(self, ids, valid):
        import contextlib

        @contextlib.contextmanager
        def cm():
            self.ids = ids
            self.ids_clip = jnp.minimum(ids, self.M - 1)
            self.valid = valid
            # padding slots all write the dummy entry M (never read: plan
            # neighbor ids are < M); out-of-cohort ids default to slot 0 —
            # finite garbage on rows whose results the schedule discards
            self.slot_of = jnp.zeros((self.M + 1,), jnp.int32).at[ids].set(
                jnp.arange(self.C, dtype=jnp.int32), mode="drop")
            try:
                yield
            finally:
                self.ids = self.ids_clip = self.valid = self.slot_of = None

        return cm()

    # ------------------------------------------------------------ randomness
    def client_keys(self, key):
        """The global M-way key split's cohort rows — client i's stream is
        independent of its cohort slot (split is not prefix-stable, so the
        full split is computed then sliced, exactly like the sharded path)."""
        return jax.random.split(key, self.M)[self.ids_clip]

    def sample_batches(self, train_x, train_y, key, batch_size):
        """Paged ``sample_client_batches``: the (M, B) index draw is
        made at full population size and row-sliced at the cohort's global
        ids, then gathered from the compact data stacks."""
        if batch_size is None:
            return train_x, train_y
        with layer("sample_batches"):
            R = train_y.shape[1]
            idx = jax.random.randint(key, (self.M, batch_size), 0,
                                     R)[self.ids_clip]
            xs = jnp.take_along_axis(
                train_x, idx.reshape(idx.shape + (1,) * (train_x.ndim - 2)),
                axis=1)
            ys = jnp.take_along_axis(train_y, idx, axis=1)
        return xs, ys

    def rows(self, v):
        """The cohort slots' view of a full (M,) vector; padding slots
        read 0, so they never merge."""
        return v[self.ids_clip] * self.valid

    # --------------------------------------------------------------- metrics
    def metric_means(self, per_client: Dict[str, Any]) -> Dict[str, Any]:
        """Scalar means over the cohort's valid rows. (Under sampling the
        resident engine means train metrics over all M clients, including
        never-aggregated local passes — the cohort mean is the documented
        paged difference; accuracy/participation/ledger metrics are computed
        elsewhere and stay bit-exact.)"""
        denom = jnp.maximum(jnp.sum(self.valid), 1.0)

        def mean(v):
            if getattr(v, "ndim", 0) >= 1 and v.shape[0] == self.C:
                return jnp.sum(v * self.valid) / denom
            return v

        return {k: mean(v) for k, v in per_client.items()}

    # --------------------------------------------------- expansion / compact
    def expand(self, tree_c):
        """Scatter-expand compact (C, ...) leaves into zeros-backed (M, ...)
        stacks (padding slots land in a dummy row and are sliced away), so a
        resident full-M reduction can run verbatim."""
        def ex(leaf):
            if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == self.C:
                buf = jnp.zeros((self.M + 1,) + leaf.shape[1:], leaf.dtype)
                return buf.at[self.ids].set(leaf, mode="drop")[: self.M]
            return leaf

        return jax.tree_util.tree_map(ex, tree_c)

    def compact_like(self, out, full_in):
        """Take the cohort rows back out of a full-M aggregation result;
        leaves whose shape changed (e.g. FedAvg's (M, ...) → global model)
        are population-independent results and pass through."""
        out_leaves, out_def = jax.tree_util.tree_flatten(out)
        full_leaves, full_def = jax.tree_util.tree_flatten(full_in)
        if out_def != full_def:
            return out
        res = []
        for o, f in zip(out_leaves, full_leaves):
            if (getattr(o, "ndim", 0) >= 1 and o.shape == f.shape
                    and o.shape[0] == self.M):
                res.append(o[self.ids_clip])
            else:
                res.append(o)
        return jax.tree_util.tree_unflatten(out_def, res)

    # ------------------------------------------------------ strategy hooks
    # (a paged chunk runs only ClientSampling bodies: every hook is masked)
    def local_update(self, strategy, state, xs, ys, r, key):
        with layer("local_update"):
            return strategy.paged_local_update(state, xs, ys, r, key, self)

    def aggregate_masked(self, strategy, state, r, key, mask, rows):
        with layer("aggregate"):
            return strategy.paged_aggregate_masked(state, r, key, mask, self)

    def _mix_step(self, tree, plan, r, key, keep, halo):
        """The resident step's per-row gossip arithmetic with neighbor
        reads resolved through the cohort's slot map: the planner closed
        the cohort over in-neighbors (``paged_cohort_closure``), so every
        participant row reads exactly the values the resident step reads."""
        from repro.topology.mixing import mix_stacked_paged
        return mix_stacked_paged(tree, plan, r, key, self, keep=keep)


class CohortPrefetcher:
    """Double-buffered host-side staging of the next chunk's cohort.

    ``submit(tag, fn)`` runs ``fn`` on a background thread while the current
    chunk executes on device; ``take(tag)`` returns the result only when the
    prediction tag matches. Staleness discipline lives with the caller: every
    prefetched payload records the population ``version`` at gather time, and
    ``PagedEngine`` re-gathers state rows whenever the version moved (a
    scatter landed in between) — a stale cohort is never served."""

    def __init__(self):
        self._pool: Optional[ThreadPoolExecutor] = None
        self._fut = None
        self._tag = None
        self.stats = {"submitted": 0, "hits": 0, "misses": 0, "stale": 0}

    def submit(self, tag, fn) -> None:
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="cohort-prefetch")
        self._tag = tag
        self._fut = self._pool.submit(fn)
        self.stats["submitted"] += 1
        PREFETCH_STATS["submitted"] += 1

    def take(self, tag):
        """The prefetched payload for ``tag``, or None on a prediction miss
        (the caller gathers synchronously)."""
        fut, got = self._fut, self._tag
        self._fut = self._tag = None
        if fut is None or got != tag:
            if fut is not None:
                fut.cancel()
            self.stats["misses"] += 1
            PREFETCH_STATS["misses"] += 1
            return None
        try:
            out = fut.result()
        except Exception:
            self.stats["misses"] += 1
            PREFETCH_STATS["misses"] += 1
            return None
        self.stats["hits"] += 1
        PREFETCH_STATS["hits"] += 1
        return out

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None
        self._fut = self._tag = None


@dataclass(eq=False)
class PagedEngine(Engine):
    """Engine whose client population is host-resident; only the active
    cohort is materialized on device per chunk.

    ``cohort_pad`` buckets the traced cohort width (cohorts pad up to a
    multiple, so varying Bernoulli draws reuse a handful of compiled chunks).
    ``mesh`` optionally shards the cohort axis over an existing clients mesh
    (``repro.launch.mesh.make_client_mesh``): compact stacks are device_put
    with a ``P(client_axis)`` sharding and the cohort width pads to the mesh
    size, letting GSPMD partition the paged body (numerically tight, not
    bit-exact — partitioned reductions reassociate).
    ``prefetch`` enables the double-buffered next-cohort gather."""

    cohort_pad: int = 8
    prefetch: bool = True
    mesh: Optional[Any] = None
    client_axis: str = "clients"

    def __post_init__(self):
        super().__post_init__()
        if self.mesh is not None and self.client_axis not in self.mesh.shape:
            raise ValueError(
                f"mesh {dict(self.mesh.shape)} has no {self.client_axis!r} "
                "axis")
        self._pop: Optional[VirtualPopulation] = None
        self._host_data: Optional[HostFederatedData] = None
        self._skeleton_leaves: Optional[List[Any]] = None
        self._M: Optional[int] = None
        self._prefetcher = CohortPrefetcher()
        self._replay_cache: Dict[Tuple, Any] = {}

    # ------------------------------------------------------------ chunk key
    def _mesh_fingerprint(self) -> Tuple:
        if self.mesh is None:
            return ()
        n = int(self.mesh.shape[self.client_axis])
        devs = tuple(d.id for d in self.mesh.devices.flat)
        return ("paged-mesh", self.client_axis, n, devs)

    def _paged_sampling(self) -> bool:
        return isinstance(self.schedule, ClientSampling)

    # --------------------------------------------------------- host planning
    def _replay_masks(self, phase_key, start: int, length: int) -> np.ndarray:
        """Host replay of the chunk's (L, M) participation draws — the exact
        streams the device body draws (fold_in(fold_in(phase_key, r), 3)),
        so the planned cohort is precisely the union of the device's sampled
        cohorts (a superset of realized participants under faults, which only
        remove clients)."""
        sched, M = self.schedule, self._M
        key_ = (self.schedule.fingerprint(), length, M)
        fn = self._replay_cache.get(key_)
        if fn is None:
            def replay(pk, start_r):
                def one(r):
                    rk = jax.random.fold_in(pk, r)
                    return sched.draw_mask(jax.random.fold_in(rk, 3), M)
                return jax.vmap(one)(start_r + jnp.arange(length))
            fn = jax.jit(replay)
            self._replay_cache[key_] = fn
        return np.asarray(fn(phase_key, jnp.asarray(start, jnp.int32)))

    def _plan_cohort(self, phase_key, start: int, stop: int) -> np.ndarray:
        """Global client ids the chunk [start, stop) must page in."""
        masks = self._replay_masks(phase_key, start, stop - start)
        ids = np.nonzero(masks.any(axis=0))[0]
        return np.asarray(self.strategy.paged_cohort_closure(
            ids, np.arange(start, stop)), np.int64)

    def _pad_cohort(self, n_real: int) -> int:
        pad = max(int(self.cohort_pad), 1)
        if self.mesh is not None:
            n = int(self.mesh.shape[self.client_axis])
            pad = pad * n // np.gcd(pad, n)
        return max(-(-n_real // pad) * pad, pad)

    # ------------------------------------------------------ gather / scatter
    def _gather_payload(self, gather_ids: np.ndarray) -> Dict[str, Any]:
        """Host-side cohort gather (runs on the prefetch thread): compact
        data rows plus the population's state rows, stamped with the
        population version for the staleness check."""
        # version read BEFORE the gather: a scatter racing the gather then
        # always trips the take-time staleness check (worst case a spurious
        # re-gather, never a stale serve)
        version = self._pop.version
        return {
            "train_x": self._host_data.train_x[gather_ids],
            "train_y": self._host_data.train_y[gather_ids],
            "state": self._pop.gather(gather_ids),
            "version": version,
        }

    def _device_put_rows(self, arr):
        if self.mesh is None:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(arr, NamedSharding(self.mesh,
                                                 P(self.client_axis)))

    def _take_cohort(self, tag, gather_ids: np.ndarray) -> Dict[str, Any]:
        """Prefetched payload if the prediction matched, else a synchronous
        gather. A prefetched payload whose population version moved (a
        scatter landed after its gather started) re-gathers the state rows —
        the data rows are immutable and stay valid, but stale state is never
        served (property-tested in tests/test_population.py)."""
        start, stop, C = tag
        payload = (self._prefetcher.take((start, stop, None))
                   if self.prefetch else None)
        if payload is None or payload.get("C") != C:
            payload = self._gather_payload(gather_ids)
        elif payload["version"] != self._pop.version:
            self._prefetcher.stats["stale"] += 1
            PREFETCH_STATS["stale"] += 1
            payload["state"] = self._pop.gather(gather_ids)
            payload["version"] = self._pop.version
        return payload

    # --------------------------------------------------------- chunk builder
    def _chunk_fn_paged(self, length: int, batch_size: Optional[int],
                        cohort: int):
        key_ = self._chunk_key(length, batch_size) + ("paged", cohort,
                                                      self._M)
        fn = _cache_get(key_)
        if fn is not None:
            return fn
        pctx = PagedCtx(self._M, cohort)
        body = self.schedule.round_body(self.strategy, batch_size, pctx)
        if self.faults is not None:
            from repro.resilience import wrap_round_body
            body = wrap_round_body(body, self.faults)
        tap = None
        if self._tap_traced():
            from repro.obs.telemetry import tap_scan
            tap = tap_scan

        def run(state, phase_key, ids, valid, train_x, train_y, start, rt):
            CHUNK_STATS["traces"] += 1
            with runtime_params(rt), pctx.installed(ids, valid):
                def scan_body(state, r):
                    return body(state, r, phase_key, train_x, train_y)

                rs = start + jnp.arange(length)
                if tap is not None:
                    return tap(scan_body, state, rs, rt)
                return jax.lax.scan(scan_body, state, rs)

        fn = jax.jit(run, donate_argnums=0)
        _cache_put(key_, fn)
        return fn

    # -------------------------------------------------------------- the loop
    def _run_chunk(self, state, data, phase_key, start: int, stop: int,
                   batch_size: Optional[int]):
        M = self._M
        paged = self._paged_sampling()
        if paged:
            ids_real = self._plan_cohort(phase_key, start, stop)
        else:
            # full-participation / async: every client trains every round —
            # the cohort is the population, and the resident round body runs
            # verbatim on the fully gathered stacks
            ids_real = np.arange(M, dtype=np.int64)
        n_real = len(ids_real)
        if paged:
            C = self._pad_cohort(n_real)
            ids_pad = np.full((C,), M, np.int32)
            ids_pad[:n_real] = ids_real
            gather_ids = np.minimum(ids_pad, M - 1).astype(np.int64)
        else:
            C = n_real
            ids_pad = ids_real.astype(np.int32)
            gather_ids = ids_real

        payload = self._take_cohort((start, stop, C), gather_ids)
        # the full-gather (resident-body) path keeps replicated placement:
        # M need not divide the mesh, and the resident chunk is reused as-is
        put = self._device_put_rows if paged else jnp.asarray
        train_x = put(payload["train_x"])
        train_y = put(payload["train_y"])
        leaves = list(self._skeleton_leaves)
        for i, leaf in enumerate(leaves):
            if isinstance(leaf, _PopLeaf):
                leaves[i] = put(payload["state"][leaf.idx])
        compact_state = jax.tree_util.tree_unflatten(self._treedef, leaves)

        rt = {k: jnp.asarray(v, jnp.float32)
              for k, v in self.strategy.runtime_params().items()}
        carry = (compact_state if self.faults is None
                 else (compact_state, self._fault_state))
        if paged:
            fn = self._build_chunk(self._chunk_fn_paged, stop - start,
                                   batch_size, C)
            carry, (metrics, aux) = self._dispatch_chunk(
                fn, (carry, phase_key, jnp.asarray(ids_pad),
                     jnp.asarray((ids_pad < M).astype(np.float32)),
                     train_x, train_y, jnp.asarray(start, jnp.int32), rt),
                start, stop, rt)
        else:
            fn = self._build_chunk(self._chunk_fn, stop - start, batch_size,
                                   data)
            carry, (metrics, aux) = self._dispatch_chunk(
                fn, (carry, phase_key, train_x, train_y,
                     jnp.asarray(start, jnp.int32), rt),
                start, stop, rt)
        if self.faults is None:
            out_state = carry
        else:
            out_state, self._fault_state = carry

        # predict the next chunk and start its host gather while the device
        # chunk is still executing (JAX dispatch is asynchronous — the
        # blocking np.asarray reads below overlap with this thread's work)
        if self.prefetch:
            nxt = (stop, stop + (stop - start))
            self._prefetcher.submit(
                nxt + (None,), lambda: self._prefetch_payload(phase_key, nxt))

        # scatter updated population rows back (blocks on the chunk)
        out_leaves = jax.tree_util.tree_flatten(out_state)[0]
        if len(self._pop) and n_real:
            pop_vals = []
            for skel, out in zip(self._skeleton_leaves, out_leaves):
                if isinstance(skel, _PopLeaf):
                    pop_vals.append((skel.idx, np.asarray(out)[:n_real]))
            pop_vals.sort(key=lambda t: t[0])
            self._pop.scatter(ids_real, [v for _, v in pop_vals])
        # non-paged leaves (server-style globals, fault carries) stay device-
        # resident across chunks
        new_skel = []
        for skel, out in zip(self._skeleton_leaves, out_leaves):
            new_skel.append(skel if isinstance(skel, _PopLeaf) else out)
        self._skeleton_leaves = new_skel
        return state, metrics, aux

    def _prefetch_payload(self, phase_key, nxt: Tuple[int, int]):
        start, stop = nxt
        M = self._M
        if self._paged_sampling():
            ids_real = self._plan_cohort(phase_key, start, stop)
            C = self._pad_cohort(len(ids_real))
            ids_pad = np.full((C,), M, np.int32)
            ids_pad[: len(ids_real)] = ids_real
            gather_ids = np.minimum(ids_pad, M - 1).astype(np.int64)
        else:
            C = M
            gather_ids = np.arange(M, dtype=np.int64)
        payload = self._gather_payload(gather_ids)
        payload["C"] = C
        return payload

    # ------------------------------------------- population representation
    def _prepare_state(self, state, data):
        self._M = M = data.num_clients
        self._host_data = as_host_data(data)
        stacked = self.strategy.state_client_stacked(state)
        leaves, treedef = jax.tree_util.tree_flatten(state)
        pop = VirtualPopulation(M)
        skel = []
        for leaf in leaves:
            arr = np.asarray(leaf)
            if stacked and arr.ndim >= 1 and arr.shape[0] == M and M > 1:
                idx = pop.add(arr.copy())
                skel.append(_PopLeaf(idx, arr.shape, arr.dtype))
            else:
                skel.append(jnp.asarray(leaf))
        self._pop = pop
        self._treedef = treedef
        self._skeleton_leaves = skel
        return jax.tree_util.tree_unflatten(treedef, skel)

    def _finalize_state(self, state):
        leaves = [jnp.asarray(self._pop.arrays[leaf.idx])
                  if isinstance(leaf, _PopLeaf) else leaf
                  for leaf in self._skeleton_leaves]
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    def _log_network(self, state, first_round, last_round, masks=None,
                     phase_key=None) -> None:
        if self.network is None:
            return
        super()._log_network(self._finalize_state(state), first_round,
                             last_round, masks=masks, phase_key=phase_key)

    # ----------------------------------------------------------- checkpoints
    def _checkpoint_rest(self, state):
        """The non-population remainder of the state (server-style globals),
        with paged leaves as zero-length placeholders so the npz template is
        shape-stable."""
        return jax.tree_util.tree_unflatten(
            self._treedef,
            [jnp.zeros((0,), leaf.dtype) if isinstance(leaf, _PopLeaf)
             else leaf for leaf in self._skeleton_leaves])

    def _save_checkpoint(self, ev: int, state, history) -> None:
        from repro import checkpoint as ck
        if len(self._pop):
            # population first, plain checkpoint last: the ckpt file is the
            # commit point (resume only considers steps whose ckpt verifies,
            # and then requires the population chain at that step to verify)
            ck.save_population(self.checkpoint_dir, ev, self._pop,
                               keep_last=self.checkpoint_keep)
        ck.save_checkpoint(self.checkpoint_dir, ev,
                           self._checkpoint_rest(state),
                           metadata={"history": {
                               "rounds": history.rounds,
                               "accuracy": history.accuracy,
                               "metrics": history.metrics},
                               "population": len(self._pop)},
                           keep_last=self.checkpoint_keep)

    def _latest_resume_step(self):
        from repro import checkpoint as ck
        for step in reversed(ck.verified_steps(self.checkpoint_dir)):
            if ck.population_chain_ok(self.checkpoint_dir, step):
                return step
        return None

    def _restore_for_resume(self, state, data, resume_step: int):
        from repro import checkpoint as ck
        saved, resume_step = ck.restore_checkpoint(
            self.checkpoint_dir, self._checkpoint_rest(state), resume_step)
        saved_leaves = jax.tree_util.tree_flatten(saved)[0]
        self._skeleton_leaves = [
            skel if isinstance(skel, _PopLeaf) else jnp.asarray(sv)
            for skel, sv in zip(self._skeleton_leaves, saved_leaves)]
        if len(self._pop):
            ck.restore_population(self.checkpoint_dir, self._pop, resume_step)
        meta = ck.load_checkpoint_metadata(self.checkpoint_dir, resume_step)
        state = jax.tree_util.tree_unflatten(self._treedef,
                                             self._skeleton_leaves)
        return state, resume_step, (meta or {}).get("history")
