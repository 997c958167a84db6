"""Mixing matrices and the in-jit gossip step.

Two layers:

  * host-side construction — Metropolis–Hastings weights (doubly stochastic
    on ANY symmetric graph), the lazy uniform rule for regular graphs (the
    DP-DSGT ring's historical 1/2–1/4–1/4 row), spectral-gap reporting, and
    connectivity checks;

  * the traced mixing step — ``make_plan`` compiles a topology into a
    ``MixPlan`` (padded neighbor-index/weight arrays plus special-case
    flags) and ``mix_stacked`` applies one gossip round to a stacked
    (M, ...) pytree inside the engine's scanned round body. The plan keeps
    three executions of the same arithmetic:

      - uniform fast path: ``s·t + w·Σ_k t[nbr_k]`` with scalar s, w —
        for the ring this is bit-identical to the pre-refactor
        ``_ring_mix`` expression ``0.5·t + 0.25·(left + right)``;
      - general path: per-row self weights + per-slot neighbor weights
        (Metropolis rows, matchings, fault-adjusted rows);
      - sharded paths (``mix_stacked_sharded``): a slice-local gather when
        every edge is shard-resident (no collective at all), a ppermute
        halo exchange of exactly the boundary rows when the graph has
        bounded bandwidth under the shard layout (``halo_schedule`` —
        rings, tori, circulant expanders, banded/clustered graphs, with
        or without fault ``keep`` masks), and the gather→mix→re-shard
        fallback (exact for any graph) otherwise. The trace-time
        ``MIX_STATS`` probe records which path ran and how many
        collectives it issued, so tests can assert "0 gathers per round"
        for banded families.

    Link faults are drawn in-jit per round (``repro.topology.faults``) and
    folded into the row weights with the dropped mass moved to the diagonal,
    so every realized matrix stays doubly stochastic — gossip under faults
    still preserves the global mean.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from repro.obs.probes import Probe

# ---------------------------------------------------------------------------
# Host-side: weight construction + graph diagnostics (numpy only — graphs.py
# imports these at module load, before jax is necessarily initialized)
# ---------------------------------------------------------------------------


def metropolis_weights(adjacency: np.ndarray) -> np.ndarray:
    """Metropolis–Hastings: W_ij = 1 / (1 + max(d_i, d_j)) on edges,
    diagonal absorbs the remainder. Symmetric + doubly stochastic on any
    symmetric graph (Xiao & Boyd 2004)."""
    adj = np.asarray(adjacency, bool)
    deg = adj.sum(axis=1)
    denom = 1.0 + np.maximum(deg[:, None], deg[None, :])
    w = np.where(adj, 1.0 / denom, 0.0)
    np.fill_diagonal(w, 0.0)
    np.fill_diagonal(w, 1.0 - w.sum(axis=1))
    return w


def uniform_weights(adjacency: np.ndarray, self_weight: float = 0.5, *,
                    allow_irregular: bool = False) -> np.ndarray:
    """Lazy uniform rule: diagonal ``s``, each edge ``(1−s)/d``. Requires a
    regular graph (that is what makes it doubly stochastic); with
    ``allow_irregular`` the edge weight becomes ``(1−s)/max(d_i, d_j)`` and
    the diagonal absorbs the remainder (used for matchings, where degrees
    are 0/1 and the two rules coincide)."""
    adj = np.asarray(adjacency, bool)
    deg = adj.sum(axis=1)
    s = float(self_weight)
    if not 0.0 <= s <= 1.0:
        raise ValueError(f"self_weight must be in [0, 1], got {s}")
    pos = deg[deg > 0]
    if pos.size == 0:
        return np.eye(adj.shape[0])
    if not allow_irregular and not np.all(pos == pos[0]):
        raise ValueError(
            "uniform weighting needs a regular graph; use "
            "weighting='metropolis' (or allow_irregular for matchings)")
    denom = np.maximum(np.maximum(deg[:, None], deg[None, :]), 1)
    w = np.where(adj, (1.0 - s) / denom, 0.0)
    np.fill_diagonal(w, 0.0)
    np.fill_diagonal(w, 1.0 - w.sum(axis=1))
    return w


def is_doubly_stochastic(w: np.ndarray, tol: float = 1e-9) -> bool:
    w = np.asarray(w, np.float64)
    return (np.all(w >= -tol)
            and np.allclose(w.sum(axis=0), 1.0, atol=1e-8)
            and np.allclose(w.sum(axis=1), 1.0, atol=1e-8))


def is_column_stochastic(w: np.ndarray, tol: float = 1e-9) -> bool:
    """Column sums 1 (every sender's outgoing shares, incl. its self-share,
    sum to 1) — the mass-conservation property push-sum mixing needs."""
    w = np.asarray(w, np.float64)
    return (np.all(w >= -tol)
            and np.allclose(w.sum(axis=0), 1.0, atol=1e-8))


def push_sum_weights(trust: np.ndarray) -> np.ndarray:
    """Column-stochastic mixing matrix from a row-stochastic trust matrix.

    Row i of ``trust`` is how client i splits its outgoing mass across the
    peers it trusts (plus itself). The mixing step is still the row gather
    ``x_i ← Σ_j W_ij x_j``, so the matrix handed to ``make_plan`` must carry
    sender j's share to receiver i at W[i, j] — i.e. ``trust.T``."""
    t = np.asarray(trust, np.float64)
    if not np.allclose(t.sum(axis=1), 1.0, atol=1e-8) or np.any(t < -1e-12):
        raise ValueError("trust matrix must be row stochastic")
    return t.T.copy()


def is_connected(adjacency: np.ndarray) -> bool:
    """BFS from node 0 (single-node graphs count as connected)."""
    adj = np.asarray(adjacency, bool)
    M = adj.shape[0]
    if M <= 1:
        return True
    seen = np.zeros(M, bool)
    seen[0] = True
    frontier = [0]
    while frontier:
        nxt = adj[frontier].any(axis=0) & ~seen
        frontier = np.nonzero(nxt)[0].tolist()
        seen |= nxt
    return bool(seen.all())


def spectral_gap(w: np.ndarray) -> float:
    """1 − |λ₂| of a mixing matrix: the per-round contraction of the
    consensus error, the quantity accuracy-vs-topology sweeps plot.
    Symmetric W uses the Hermitian solver; directed (learned) W falls back
    to the general eigenvalue problem on |λ|."""
    w = np.asarray(w, np.float64)
    if w.shape[0] <= 1:
        return 1.0
    if np.allclose(w, w.T, atol=1e-12):
        lam = np.sort(np.abs(np.linalg.eigvalsh(w)))[::-1]
    else:
        lam = np.sort(np.abs(np.linalg.eigvals(w)))[::-1]
    return float(1.0 - lam[1])


# ---------------------------------------------------------------------------
# The traced mixing step
# ---------------------------------------------------------------------------


@dataclass(eq=False)
class MixPlan:
    """A topology compiled for the scanned round body: numpy neighbor
    index/weight stacks (baked into the trace as constants) + the
    special-case flags the apply functions branch on at trace time."""

    topology: object              # the Topology / TimeVaryingTopology source
    M: int
    degree: int                   # max slots per row (padded with self-loops)
    period: int                   # 1 for static topologies
    nbr_np: np.ndarray            # (T, M, d) int32
    nbr_w_np: np.ndarray          # (T, M, d) float32, 0 on padding
    self_w_np: np.ndarray         # (T, M) float32
    uniform: Optional[Tuple[float, float]]   # (self_w, nbr_w) scalars
    ring: bool                    # shard-aligned halo exchange eligible
    drop_prob: float
    churn_prob: float
    # optional KernelConfig: opts the halo mix step's row blocking into the
    # dispatch autotuner (None => always the untiled lowering)
    kernels: Optional[object] = None
    # push-sum mode: W is column- but not row-stochastic (directed/learned
    # graphs). The gather arithmetic is unchanged; consumers must carry a
    # per-node weight scalar through the same mix and de-bias by it.
    push_sum: bool = False
    # (T, M, d) outgoing shares W[nbr_k, i] aligned with nbr_np's slots —
    # what row i SENDS to each listed neighbor. Only set under push-sum:
    # the fault fold must return a dropped link's mass to the sender's
    # diagonal (not the receiver's) to keep the realized matrix
    # column-stochastic.
    out_w_np: Optional[np.ndarray] = None

    @property
    def faulty(self) -> bool:
        return self.drop_prob > 0.0 or self.churn_prob > 0.0


def make_plan(topology, kernels=None, push_sum: Optional[bool] = None) -> MixPlan:
    """Compile a (possibly time-varying) topology into a MixPlan.

    Row-stochastic W compiles to the standard averaging plan. A W that is
    column- but not row-stochastic (directed/learned graphs) compiles to a
    push-sum plan automatically; pass ``push_sum=True`` to force push-sum
    on a doubly-stochastic W (it converges to the same fixed point — the
    weight scalar stays ≈ 1)."""
    topos = getattr(topology, "topologies", None) or [topology]
    M = topos[0].M
    d = max((int(t.degrees.max()) if t.M and t.num_edges else 0)
            for t in topos)
    T = len(topos)
    nbr = np.tile(np.arange(M, dtype=np.int32)[None, :, None], (T, 1, max(d, 1)))
    nbr_w = np.zeros((T, M, max(d, 1)), np.float32)
    self_w = np.ones((T, M), np.float32)
    for t, topo in enumerate(topos):
        w = topo.weights
        for i in range(M):
            js = np.nonzero(topo.adjacency[i])[0]
            nbr[t, i, : len(js)] = js
            nbr_w[t, i, : len(js)] = w[i, js].astype(np.float32)
            self_w[t, i] = np.float32(w[i, i])

    row_ok = all(np.allclose(t.weights.sum(axis=1), 1.0, atol=1e-6)
                 for t in topos)
    col_ok = all(np.allclose(t.weights.sum(axis=0), 1.0, atol=1e-6)
                 for t in topos)
    if push_sum is None:
        push_sum = col_ok and not row_ok
    if push_sum and not col_ok:
        raise ValueError(
            "push-sum mixing needs a column-stochastic W (every sender's "
            "outgoing shares must sum to 1)")
    if not push_sum and not row_ok:
        raise ValueError(
            "standard mixing needs a row-stochastic W; a directed "
            "column-stochastic W (learned graphs) mixes via push-sum — "
            "make_plan detects this automatically, so the weights here are "
            "neither row- nor column-stochastic")

    out_w = None
    uniform = None
    ring = False
    if push_sum:
        # the sender-side share stack: out_w[t, i, k] is what row i ships to
        # nbr[t, i, k] — W[nbr_k, i]. Slot padding (self-loop index, zero
        # in-weight) gets a zero out-share too: padded slots carry no mass.
        out_w = np.zeros_like(nbr_w)
        for t, topo in enumerate(topos):
            w = topo.weights
            for i in range(M):
                js = np.nonzero(topo.adjacency[i])[0]
                out_w[t, i, : len(js)] = w[js, i].astype(np.float32)
    else:
        # uniform fast path: one scalar self weight + one scalar edge weight
        # and a full (regular) slot occupancy everywhere — the precondition
        # for the coefficient-after-sum expression the bit-exact ring
        # contract needs
        pos_w = nbr_w[nbr_w > 0]
        if (d > 0 and pos_w.size == T * M * d
                and np.all(pos_w == pos_w.flat[0])
                and np.all(self_w == self_w.flat[0])):
            uniform = (float(self_w.flat[0]), float(pos_w.flat[0]))

        ring = bool(
            uniform is not None and d == 2 and T == 1 and M > 2
            and all(set(nbr[0, i]) == {(i - 1) % M, (i + 1) % M}
                    for i in range(M)))
    return MixPlan(topology=topology, M=M, degree=d, period=T,
                   nbr_np=nbr, nbr_w_np=nbr_w, self_w_np=self_w,
                   uniform=uniform, ring=ring,
                   drop_prob=float(getattr(topology, "drop_prob", 0.0)),
                   churn_prob=float(getattr(topology, "churn_prob", 0.0)),
                   kernels=kernels, push_sum=bool(push_sum), out_w_np=out_w)


def _round_slice(arr: np.ndarray, r, period: int):
    """Select the round's (M, ...) slab from a (T, M, ...) stack; static
    topologies skip the dynamic index entirely."""
    import jax
    import jax.numpy as jnp
    if period == 1:
        return jnp.asarray(arr[0])
    return jax.lax.dynamic_index_in_dim(jnp.asarray(arr), jnp.mod(r, period),
                                        0, keepdims=False)


def _fault_adjusted_rows(plan: MixPlan, nbr, r, key, keep=None):
    """(self_w, nbr_w) rows for round r with this round's fault realization
    folded in: dropped slots zeroed, their mass moved to the diagonal — the
    realized matrix stays symmetric doubly stochastic. Under push-sum the
    diagonal refund instead uses the OUTGOING shares (``plan.out_w_np``):
    with symmetric keep realizations a dropped link removes W[i,j]·x_j from
    receiver i and returns i's own undeliverable share W[j,i]·x_i to i, so
    every realized column still sums to one (mass conservation, the
    invariant push-sum's ratio estimate rests on). An explicit ``keep``
    (a correlated process realization from ``repro.resilience``) supersedes
    the plan's i.i.d. draw."""
    import jax.numpy as jnp
    from repro.topology.faults import draw_fault_masks
    w_row = _round_slice(plan.nbr_w_np, r, plan.period)
    s_row = _round_slice(plan.self_w_np, r, plan.period)
    if keep is None:
        if not plan.faulty:
            return s_row, w_row
        keep, _up = draw_fault_masks(key, plan.M, plan.drop_prob,
                                     plan.churn_prob)
    keep_slots = keep[jnp.arange(plan.M)[:, None], nbr]
    fold_w = (w_row if plan.out_w_np is None
              else _round_slice(plan.out_w_np, r, plan.period))
    s_row = s_row + jnp.sum(fold_w * (1.0 - keep_slots), axis=1)
    return s_row, w_row * keep_slots


def mix_stacked(tree, plan: MixPlan, r=0, key=None, keep=None):
    """One gossip round on a stacked (M, ...) pytree: t ← W_r t, with W_r
    the round's (fault-realized) mixing matrix, evaluated as a sparse
    neighbor gather. ``r`` and ``key`` may be traced (the engine passes the
    round index and the local-update key). ``keep`` is an optional external
    (M, M) edge realization (correlated fault process) that forces the
    general fault-folding path."""
    import jax
    import jax.numpy as jnp
    if plan.degree == 0 or plan.M <= 1:
        return tree

    if plan.ring and not plan.faulty and keep is None:
        # the pre-refactor ``_ring_mix`` lowering, verbatim — roll-based
        # neighbor reads keep the XLA fusion (and therefore the float
        # rounding) bit-identical to the historical DP-DSGT trajectories
        s, w = plan.uniform

        def mix_r(t):
            return s * t + w * (jnp.roll(t, 1, axis=0)
                                + jnp.roll(t, -1, axis=0))

        return jax.tree_util.tree_map(mix_r, tree)

    nbr = _round_slice(plan.nbr_np, r, plan.period)

    if plan.uniform is not None and not plan.faulty and keep is None:
        s, w = plan.uniform

        def mix_u(t):
            acc = t[nbr[:, 0]]
            for k in range(1, plan.degree):
                acc = acc + t[nbr[:, k]]
            return s * t + w * acc        # the same coefficient-after-sum shape

        return jax.tree_util.tree_map(mix_u, tree)

    s_row, w_row = _fault_adjusted_rows(plan, nbr, r, key, keep=keep)

    def mix_g(t):
        ex = (-1,) + (1,) * (t.ndim - 1)
        acc = s_row.reshape(ex) * t
        for k in range(plan.degree):
            acc = acc + w_row[:, k].reshape(ex) * t[nbr[:, k]]
        return acc.astype(t.dtype)

    return jax.tree_util.tree_map(mix_g, tree)


def plan_in_neighbors(plan: MixPlan, ids, rounds):
    """Host-side cohort closure: ``ids`` plus every positive-weight
    in-neighbor those rows read in any of ``rounds``'s period slices.
    Fault realizations only *remove* edges, so this is always a superset of
    the rows a realized chunk actually reads."""
    ids = np.asarray(ids, np.int64)
    if plan.degree == 0 or plan.M <= 1 or ids.size == 0:
        return ids
    if plan.period == 1:
        ts = [0]
    else:
        ts = sorted({int(r) % plan.period for r in np.asarray(rounds)})
    out = set(ids.tolist())
    for t in ts:
        nbr = plan.nbr_np[t][ids]
        live = plan.nbr_w_np[t][ids] > 0
        out.update(int(j) for j in nbr[live].ravel())
    return np.asarray(sorted(out), np.int64)


def mix_stacked_paged(tree, plan: MixPlan, r, key, pctx, keep=None):
    """Paged twin of ``mix_stacked``: one gossip round on a compact cohort
    (C, ...) pytree. Each cohort row applies the SAME per-row expression the
    resident step applies to its global row — neighbor reads are resolved
    through ``pctx.slot_of`` (global id → cohort slot), and fault-adjusted
    row weights are computed at full M (replicated arithmetic) then sliced at
    the cohort's rows — so participant rows are bit-identical to the resident
    mix (two-term float adds are bitwise commutative, which covers the
    resident ring's roll-based lowering). Rows whose neighbors fall outside
    the cohort read finite garbage (slot 0); the cohort planner guarantees
    those rows are non-participants, whose mixed values the schedule's
    ``merge_participation`` discards."""
    import jax
    import jax.numpy as jnp
    if plan.degree == 0 or plan.M <= 1:
        return tree
    nbr = _round_slice(plan.nbr_np, r, plan.period)      # (M, d) global ids
    rows = pctx.ids_clip                                 # (C,) global ids
    slot = pctx.slot_of[nbr[rows]]                       # (C, d) cohort slots

    if plan.uniform is not None and not plan.faulty and keep is None:
        s, w = plan.uniform

        def mix_u(t):
            acc = t[slot[:, 0]]
            for k in range(1, plan.degree):
                acc = acc + t[slot[:, k]]
            return s * t + w * acc

        return jax.tree_util.tree_map(mix_u, tree)

    s_full, w_full = _fault_adjusted_rows(plan, nbr, r, key, keep=keep)
    s_row, w_row = s_full[rows], w_full[rows]

    def mix_g(t):
        ex = (-1,) + (1,) * (t.ndim - 1)
        acc = s_row.reshape(ex) * t
        for k in range(plan.degree):
            acc = acc + w_row[:, k].reshape(ex) * t[slot[:, k]]
        return acc.astype(t.dtype)

    return jax.tree_util.tree_map(mix_g, tree)


# ---------------------------------------------------------------------------
# Push-sum mixing (directed / learned graphs, column-stochastic W)
# ---------------------------------------------------------------------------
#
# A learned collaboration graph is generally directed and only
# column-stochastic: sender j splits its unit mass across the peers it
# trusts. Plain averaging with such a W biases every estimate toward
# high-in-degree nodes. Push-sum (Kempe et al. 2003; gradient-push,
# Nedić & Olshevsky 2016) fixes this with one extra scalar per node: mix a
# weight w (initialized to 1) with the SAME matrix as the values and read
# the de-biased estimate x/w — on a strongly-connected W the ratio
# converges to the uniform average because both numerator and denominator
# pick up the same Perron re-weighting. When W happens to be doubly
# stochastic, w stays exactly 1 up to float rounding and push-sum reduces
# to the symmetric path.


def push_sum_mix(tree, weights, plan: MixPlan, r=0, key=None, keep=None):
    """One push-sum gossip round: returns ``(tree', weights')`` with both the
    stacked (M, ...) value tree and the (M,) weight scalars mixed by the
    round's realized matrix. The weights ride as one more leaf through
    ``mix_stacked`` so fault folding, time variation, and the round's keep
    mask apply to values and weights identically (the invariant de-biasing
    needs)."""
    mixed = mix_stacked({"v": tree, "w": weights}, plan, r, key, keep=keep)
    return mixed["v"], mixed["w"]


def push_sum_mix_sharded(tree, weights, plan: MixPlan, r, key, ctx,
                         keep=None, halo=None):
    """Sharded twin of ``push_sum_mix`` — same joint-leaf trick through
    ``mix_stacked_sharded``, so the halo/local/gather path selection and the
    MIX_STATS probe see one mix call for values + weights together."""
    mixed = mix_stacked_sharded({"v": tree, "w": weights}, plan, r, key, ctx,
                                keep=keep, halo=halo)
    return mixed["v"], mixed["w"]


def push_sum_mix_paged(tree, weights, plan: MixPlan, r, key, pctx, keep=None):
    """Paged twin of ``push_sum_mix`` for cohort-resident (C, ...) trees."""
    mixed = mix_stacked_paged({"v": tree, "w": weights}, plan, r, key, pctx,
                              keep=keep)
    return mixed["v"], mixed["w"]


def push_sum_debias(tree, weights):
    """The push-sum estimate: every stacked leaf divided by its row's weight
    scalar (x/w), cast back to the leaf dtype."""
    import jax
    import jax.numpy as jnp

    def one(t):
        ex = (-1,) + (1,) * (t.ndim - 1)
        return (t / jnp.asarray(weights).reshape(ex)).astype(t.dtype)

    return jax.tree_util.tree_map(one, tree)


# ---------------------------------------------------------------------------
# Sharded execution (inside a shard_map region over the client axis)
# ---------------------------------------------------------------------------

# Trace-time collective probe (``CHUNK_STATS``-style counters): every sharded
# mix records the path it took and the collectives it issued while tracing.
# The scanned round body traces once per compiled chunk, so a snapshot delta
# around a sharded run counts collectives PER ROUND — the sharded equivalence
# tier asserts 0 all_gathers/round for banded/clustered/torus families.
# A registry-backed Probe (still a plain dict to every existing caller).
# Nothing here auto-resets between Engine instances — counters accumulate
# for the life of the process — so per-run numbers go through the scoped
# API: ``repro.obs.probe_deltas("topology.mix")``.
MIX_STATS = Probe("topology.mix", {
    "calls": 0,
    "path_identity": 0, "path_local": 0, "path_halo": 0, "path_gather": 0,
    "all_gathers": 0,   # gather-fallback all_gather collectives (one per leaf)
    "ppermutes": 0,     # halo-exchange ppermute collectives (leaf × hop)
})


def mix_stats_snapshot():
    return MIX_STATS.snapshot()


def reset_mix_stats() -> None:
    MIX_STATS.reset()


def edges_shard_resident(plan: MixPlan, ctx) -> bool:
    """Host-side layout check: every positive-weight edge stays inside one
    mesh slice of ``ctx.m`` rows — mixing then needs no collective at all
    (the topology twin of P4's pod-resident groups)."""
    if plan.period != 1:
        return False
    m = ctx.m
    rows = np.arange(plan.M)[:, None]
    live = plan.nbr_w_np[0] > 0
    return bool(np.all(~live | (rows // m == plan.nbr_np[0] // m)))


@dataclass
class HaloSchedule:
    """A gather-free exchange plan for one (plan, mesh layout) pair: which
    local rows every slice ppermutes to each mesh displacement, and where
    every neighbor row lands in the per-slice receive buffer."""

    sends: Tuple                  # ((disp, (k_d,) int32 local rows), ...)
    H: int                        # total halo rows received per slice
    buf_idx: np.ndarray           # (T, M_pad, degree) int32 positions into
                                  # the (m + H, ...) [local ‖ halo] buffer


def _build_halo_schedule(plan: MixPlan, n: int, m: int) -> Optional[HaloSchedule]:
    """Derive the ppermute halo schedule from the graph's bandwidth under an
    ``n`` slices × ``m`` rows shard layout.

    For each mesh displacement ``d`` the send set is the UNION over slices of
    the local boundary rows some neighbor slice ``d`` hops ahead needs — the
    same local indices on every slice, which is what keeps the exchange SPMD
    (a single ppermute per displacement moves every slice's boundary). For
    time-varying plans the union also runs over the period, so the transfer
    pattern is trace-static and only the per-round row weights vary.

    Returns None when no exchange is needed (every edge shard-resident) or
    when the halo would be as wide as a gather (H ≥ M_pad − m: dense rows,
    e.g. Erdős–Rényi at small m) — callers fall back accordingly."""
    if n <= 1 or plan.degree == 0:
        return None
    M, deg, T = plan.M, plan.degree, plan.period
    M_pad = n * m
    send_sets = [set() for _ in range(n)]           # indexed by displacement
    for t in range(T):
        for i in range(M):
            p = i // m
            for k in range(deg):
                if plan.nbr_w_np[t, i, k] <= 0:
                    continue
                j = int(plan.nbr_np[t, i, k])
                q = j // m
                if q != p:
                    send_sets[(p - q) % n].add(j - q * m)
    sends = tuple((d, np.asarray(sorted(send_sets[d]), np.int32))
                  for d in range(1, n) if send_sets[d])
    H = sum(len(idx) for _, idx in sends)
    if H == 0 or H >= M_pad - m:
        return None
    offsets, pos_in, off = {}, {}, m
    for d, idx in sends:
        offsets[d] = off
        pos_in[d] = {int(v): i for i, v in enumerate(idx)}
        off += len(idx)
    buf_idx = np.zeros((T, M_pad, deg), np.int32)
    for t in range(T):
        for i in range(M_pad):
            p, li = divmod(i, m)
            for k in range(deg):
                if i >= M or plan.nbr_w_np[t, i, k] <= 0:
                    buf_idx[t, i, k] = li       # zero-weight slots self-loop
                    continue
                j = int(plan.nbr_np[t, i, k])
                q, lj = divmod(j, m)
                if q == p:
                    buf_idx[t, i, k] = lj
                else:
                    d = (p - q) % n
                    buf_idx[t, i, k] = offsets[d] + pos_in[d][lj]
    return HaloSchedule(sends=sends, H=H, buf_idx=buf_idx)


def halo_schedule(plan: MixPlan, ctx) -> Optional[HaloSchedule]:
    """The plan's halo schedule for ``ctx``'s layout (memoized on the plan:
    schedule construction is O(T·M·degree) host work)."""
    cache = plan.__dict__.setdefault("_halo_cache", {})
    key = (ctx.n, ctx.m)
    if key not in cache:
        cache[key] = _build_halo_schedule(plan, ctx.n, ctx.m)
    return cache[key]


def select_mix_path(plan: MixPlan, ctx) -> str:
    """Host-side dispatch predicate for the sharded mix — the single source
    of truth shared by ``mix_stacked_sharded`` and the overlap prefetch
    (``halo_start`` callers), and what tier-1 tests assert without tracing:
    ``identity`` | ``local`` | ``halo`` | ``gather``."""
    if plan.degree == 0 or plan.M <= 1:
        return "identity"
    if edges_shard_resident(plan, ctx):
        return "local"
    if halo_schedule(plan, ctx) is not None:
        return "halo"
    return "gather"


def _halo_exchange(t, sched: HaloSchedule, ctx):
    """Issue the schedule's ppermutes for one leaf: the (H, ...) halo block
    this slice receives, concatenated in send order."""
    import jax
    import jax.numpy as jnp
    parts = []
    for disp, idx in sched.sends:
        perm = [(s, (s + disp) % ctx.n) for s in range(ctx.n)]
        parts.append(jax.lax.ppermute(t[jnp.asarray(idx)], ctx.axis, perm))
        MIX_STATS["ppermutes"] += 1
    return jnp.concatenate(parts, axis=0)


def halo_start(tree, plan: MixPlan, ctx):
    """Kick off a round's boundary transfer ahead of time (the overlap half
    of the halo path): returns the halo-block tree that
    ``mix_stacked_sharded(..., halo=...)`` consumes. Rows are sent RAW and
    the (possibly fault-adjusted) row weights are applied at consume time,
    so a prefetched halo stays exact under ``keep`` masks. Only call when
    ``select_mix_path(plan, ctx) == "halo"``."""
    import jax
    sched = halo_schedule(plan, ctx)
    return jax.tree_util.tree_map(
        lambda t: _halo_exchange(t, sched, ctx), tree)


def _halo_tile(plan: MixPlan, ctx, t, sched) -> int:
    """Row-block width for the halo mix arithmetic on leaf ``t`` — resolved
    through the dispatch autotuner's cached search when the plan carries a
    KernelConfig (``make_plan(topology, kernels=...)``), untiled otherwise.
    Every width is bit-identical (per-row arithmetic); only the lowering's
    gather granularity changes."""
    if plan.kernels is None:
        return 0
    from repro.kernels.dispatch import mix_halo_tiles, resolve_backend
    feat = int(np.prod(t.shape[1:])) if t.ndim > 1 else 1
    (tm,) = mix_halo_tiles((ctx.m, sched.H, plan.degree, feat), t.dtype,
                           plan.kernels, resolve_backend(plan.kernels.backend))
    return int(tm)


def _row_blocks(m: int, tm: int):
    """Static row slices: one full slice when untiled, else ``tm``-row
    blocks (last one ragged)."""
    if tm <= 0 or tm >= m:
        return [slice(None)]
    return [slice(i0, min(i0 + tm, m)) for i0 in range(0, m, tm)]


def _halo_mix(tree, plan: MixPlan, r, key, ctx, keep=None, halo=None):
    """Gather-free sparse mix: ppermute only the boundary rows the schedule
    derived, then run the single-device per-row arithmetic against the
    (m + H, ...) receive buffer — value-identical reads in the identical
    slot-accumulation order, so the result matches the single-device step to
    the commutativity of each two-term float add. ``halo`` is an optional
    prefetched halo-block tree (issued by ``halo_start`` at the end of the
    previous round body — the double-buffered overlap path). The per-row
    arithmetic runs in row blocks sized by the dispatch autotuner when the
    plan carries a KernelConfig (``_halo_tile``); the untiled default is
    today's lowering, verbatim."""
    import jax
    import jax.numpy as jnp
    sched = halo_schedule(plan, ctx)
    local_idx = ctx.rows(
        _round_slice(sched.buf_idx, r, plan.period))    # (m, degree) slots

    def apply(mix_fn):
        if halo is None:
            return jax.tree_util.tree_map(
                lambda t: mix_fn(t, _halo_exchange(t, sched, ctx)), tree)
        return jax.tree_util.tree_map(mix_fn, tree, halo)

    if plan.uniform is not None and not plan.faulty and keep is None:
        s, w = plan.uniform

        def mix_u(t, hblock):
            buf = jnp.concatenate([t, hblock], axis=0)

            def block(sl):
                acc = buf[local_idx[sl, 0]]
                for k in range(1, plan.degree):
                    acc = acc + buf[local_idx[sl, k]]
                return s * t[sl] + w * acc

            blocks = [block(sl)
                      for sl in _row_blocks(t.shape[0],
                                            _halo_tile(plan, ctx, t, sched))]
            return blocks[0] if len(blocks) == 1 else jnp.concatenate(
                blocks, axis=0)

        return apply(mix_u)

    M, d = plan.M, plan.degree
    s_full, w_full = _fault_adjusted_rows(
        plan, _round_slice(plan.nbr_np, r, plan.period), r, key, keep=keep)
    s_row = ctx.rows(jnp.concatenate(
        [s_full, jnp.ones((ctx.M_pad - M,), s_full.dtype)]) if ctx.M_pad != M
        else s_full)
    w_row = ctx.rows(jnp.concatenate(
        [w_full, jnp.zeros((ctx.M_pad - M, d), w_full.dtype)])
        if ctx.M_pad != M else w_full)

    def mix_g(t, hblock):
        buf = jnp.concatenate([t, hblock], axis=0)
        ex = (-1,) + (1,) * (t.ndim - 1)

        def block(sl):
            acc = s_row[sl].reshape(ex) * t[sl]
            for k in range(d):
                acc = acc + w_row[sl, k].reshape(ex) * buf[local_idx[sl, k]]
            return acc.astype(t.dtype)

        blocks = [block(sl)
                  for sl in _row_blocks(t.shape[0],
                                        _halo_tile(plan, ctx, t, sched))]
        return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks,
                                                                  axis=0)

    return apply(mix_g)


def _pad_rows_np(arr: np.ndarray, target: int, fill):
    if arr.shape[0] == target:
        return arr
    pad = np.full((target - arr.shape[0],) + arr.shape[1:], fill,
                  arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def _local_mix(tree, plan: MixPlan, r, key, ctx, keep=None):
    """Slice-local gather mix for shard-resident topologies: global neighbor
    indices are localized against the shard offset; padded rows self-loop
    with zero weight. Same per-row arithmetic as the single-device paths."""
    import jax.numpy as jnp
    import jax
    M, d = plan.M, plan.degree
    nbr_pad = _pad_rows_np(plan.nbr_np[0].astype(np.int32), ctx.M_pad, 0)
    for i in range(M, ctx.M_pad):
        nbr_pad[i] = i          # padded slots reference themselves
    local_nbr = (ctx.rows(jnp.asarray(nbr_pad))
                 - ctx.shard_offset())

    if plan.uniform is not None and not plan.faulty and keep is None:
        s, w = plan.uniform

        def mix_u(t):
            acc = t[local_nbr[:, 0]]
            for k in range(1, d):
                acc = acc + t[local_nbr[:, k]]
            return s * t + w * acc

        return jax.tree_util.tree_map(mix_u, tree)

    s_full, w_full = _fault_adjusted_rows(plan, jnp.asarray(plan.nbr_np[0]),
                                          r, key, keep=keep)
    s_row = ctx.rows(jnp.concatenate(
        [s_full, jnp.ones((ctx.M_pad - M,), s_full.dtype)]) if ctx.M_pad != M
        else s_full)
    w_row = ctx.rows(jnp.concatenate(
        [w_full, jnp.zeros((ctx.M_pad - M, d), w_full.dtype)])
        if ctx.M_pad != M else w_full)

    def mix_g(t):
        ex = (-1,) + (1,) * (t.ndim - 1)
        acc = s_row.reshape(ex) * t
        for k in range(d):
            acc = acc + w_row[:, k].reshape(ex) * t[local_nbr[:, k]]
        return acc.astype(t.dtype)

    return jax.tree_util.tree_map(mix_g, tree)


def mix_stacked_sharded(tree, plan: MixPlan, r, key, ctx, keep=None,
                        halo=None):
    """Sharded twin of ``mix_stacked`` (call inside the shard_map region) —
    path selection is host-side (``select_mix_path``) and recorded by the
    ``MIX_STATS`` probe:

      all live edges shard-resident → slice-local gather (no collective);
      bounded-bandwidth graph       → ppermute halo exchange of exactly the
                                      boundary rows (``halo_schedule``). This
                                      subsumes the old shard-aligned-ring
                                      special case and composes with fault
                                      ``keep`` masks: dropped mass moves to
                                      the diagonal locally, no collective
                                      beyond the same boundary rows;
      anything else                 → all_gather → mix → re-shard, which is
                                      bit-exact with the single-device step
                                      by construction.

    Fault draws are replicated (every shard draws the identical (M, M) keep
    matrix from the same key) so realized topologies agree across layouts;
    an external correlated ``keep`` realization is replicated by the same
    argument (the fault carry is stepped identically on every slice).
    ``halo`` is an optional prefetched halo-block tree from ``halo_start``
    (the compute/communication overlap path); only the halo path consumes it.
    """
    import jax
    MIX_STATS["calls"] += 1
    path = select_mix_path(plan, ctx)
    MIX_STATS["path_" + path] += 1
    if path == "identity":
        return tree
    if path == "local":
        return _local_mix(tree, plan, r, key, ctx, keep=keep)
    if path == "halo":
        return _halo_mix(tree, plan, r, key, ctx, keep=keep, halo=halo)
    MIX_STATS["all_gathers"] += len(jax.tree_util.tree_leaves(tree))
    full = ctx.gather(tree)
    return ctx.scatter_like(mix_stacked(full, plan, r, key, keep=keep), full)
