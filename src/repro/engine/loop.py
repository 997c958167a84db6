"""The federated-simulation engine: one device-resident round loop for all
methods.

The legacy trainers each hand-rolled a Python ``for r in range(rounds)`` loop
with host-side numpy batch sampling — every round paid one dispatch plus an
H2D transfer of M×B×D batch data. Here the loop is the fast path:

  * batch indices are drawn with ``jax.random`` *inside* the jitted step and
    gathered from the device-resident ``(M, R, ...)`` training stacks — no
    per-round host↔device traffic at all;
  * rounds are chunked under ``jax.lax.scan`` between eval points, with the
    state carry donated, so a 100-round sweep is a handful of XLA calls
    rather than hundreds of Python dispatches;
  * every method shares the same eval cadence and ``History`` record, so
    trainers can only differ in their Strategy hooks.

Per-round randomness is derived as ``fold_in(phase_key, r)`` — a Python loop
driving the same round body reproduces the scan bit-for-bit (tested in
``tests/test_engine.py``), which is what makes the refactor safe.

The round body itself is owned by a ``RoundSchedule`` (``engine/schedule.py``):
full participation (the body above, verbatim), client sampling (cohorts drawn
inside the jit), or staleness-buffered async aggregation. An optional
``PrivacyLedger`` (``engine/accounting.py``) is advanced per executed chunk
and its cumulative (ε, δ) lands in ``History.metrics`` at every eval round.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.accounting import PrivacyLedger
from repro.engine.schedule import (FullParticipation, ResidentCtx,
                                   RoundSchedule, sample_client_batches)
from repro.engine.strategy import (FederatedData, Strategy, runtime_params)
from repro.obs.layers import note_dispatch
from repro.obs.probes import Probe
from repro.obs.spans import span


# ---------------------------------------------------------------------------
# Compiled-chunk cache: GLOBAL (cross-Engine-instance), keyed by value
# fingerprints. A sweep that builds a fresh (strategy, Engine) pair per ε/σ
# point reuses the chunk compiled at the first point — σ reaches the trace as
# a runtime argument (see engine.strategy.runtime_params), so only changes
# that alter the traced computation (groups, schedule, lr, DP on/off, mesh,
# chunk length) miss. Bounded LRU; ``CHUNK_STATS["traces"]`` counts actual
# retraces (the probe the regression tests assert on).
# ---------------------------------------------------------------------------

CHUNK_CACHE: "OrderedDict[Tuple, Any]" = OrderedDict()
CHUNK_CACHE_MAX = 128
# a registry-backed Probe (still a dict: every existing read/increment works
# verbatim) — ``repro.obs.probe_deltas("engine.chunk_cache")`` scopes it
CHUNK_STATS = Probe("engine.chunk_cache", {"traces": 0, "hits": 0,
                                           "misses": 0})


def clear_chunk_cache() -> None:
    CHUNK_CACHE.clear()
    CHUNK_STATS.update(traces=0, hits=0, misses=0)


def _cache_get(key):
    fn = CHUNK_CACHE.get(key)
    if fn is not None:
        CHUNK_CACHE.move_to_end(key)
        CHUNK_STATS["hits"] += 1
    return fn


def _cache_put(key, fn) -> None:
    CHUNK_STATS["misses"] += 1
    CHUNK_CACHE[key] = fn
    while len(CHUNK_CACHE) > CHUNK_CACHE_MAX:
        CHUNK_CACHE.popitem(last=False)


@dataclass
class History:
    """Unified metrics record shared by every trainer."""
    rounds: List[int] = field(default_factory=list)
    accuracy: List[float] = field(default_factory=list)
    metrics: Dict[str, List[float]] = field(default_factory=dict)

    @staticmethod
    def _scalar(key: str, v) -> float:
        """Validate one recorded value: plain scalars and 0-d arrays pass;
        anything else (stray (1,)-arrays, traced values, objects) raises
        naming the offending metric key instead of dying with an opaque
        ``TypeError`` deep in the loop."""
        if isinstance(v, (bool, int, float)):
            return float(v)
        try:
            arr = np.asarray(v)
        except Exception as e:  # e.g. a jax tracer leaking out of a jit
            raise TypeError(
                f"History.record: metric {key!r} is not a concrete scalar "
                f"(got {type(v).__name__}: {v!r})") from e
        if arr.ndim == 0 and arr.dtype != object:
            return float(arr)
        raise TypeError(
            f"History.record: metric {key!r} must be a scalar or 0-d array, "
            f"got shape {arr.shape} dtype {arr.dtype} — reduce it (e.g. "
            f"jnp.mean) before recording")

    def record(self, r: int, acc: float, metrics: Optional[Dict[str, float]] = None):
        self.rounds.append(int(r))
        self.accuracy.append(self._scalar("accuracy", acc))
        for k, v in (metrics or {}).items():
            self.metrics.setdefault(k, []).append(self._scalar(k, v))

    def as_tuples(self) -> List[Tuple[int, float]]:
        """Legacy ``[(round, mean_accuracy)]`` shape used by benchmarks."""
        return list(zip(self.rounds, self.accuracy))

    def last(self) -> Tuple[int, float]:
        return self.rounds[-1], self.accuracy[-1]

    # sequence protocol: drop-in for the legacy [(round, acc)] histories
    def __len__(self) -> int:
        return len(self.rounds)

    def __getitem__(self, i):
        return self.as_tuples()[i]

    def __iter__(self):
        return iter(self.as_tuples())


def eval_rounds(start: int, rounds: int, eval_every: int) -> List[int]:
    """The legacy cadence: after round r when r % eval_every == 0, plus the
    final round — preserved exactly so histories line up across the port."""
    ev = max(int(eval_every), 1)
    out = [r for r in range(start, rounds) if r % ev == 0]
    if rounds - 1 >= start and (rounds - 1) not in out:
        out.append(rounds - 1)
    return out


@dataclass(eq=False)  # identity hash: instances close over jitted chunks
class Engine:
    """Owns the round loop; the strategy owns the method.

    Optional hooks:
      network         — a ``repro.core.p2p.P2PNetwork``; at each eval boundary
                        the strategy's ``log_communication`` is invoked for
                        every elapsed round, so §4.5 byte/message accounting
                        falls out of the same loop as training.
      checkpoint_dir  — save the strategy state at every eval point and
                        resume from the latest checkpoint via ``fit(resume=True)``.
      schedule        — a ``RoundSchedule`` owning the scanned round body
                        (default FullParticipation: the PR-2 body, verbatim).
      ledger          — a ``PrivacyLedger``; advanced per executed chunk, its
                        cumulative (ε, δ) is recorded in ``History.metrics``
                        at every eval round.
      faults          — a ``repro.resilience.FaultProcess``; its Markov state
                        rides the scan carry, each round's realization reaches
                        the schedule/strategy via the trace-time fault context,
                        and host-side replay re-derives the exact masks for
                        byte accounting and crash-resume fast-forward.
      checkpoint_keep — retain only the newest k checkpoints (0 = keep all).
      telemetry       — a ``repro.obs.Telemetry``; spans chunk dispatch,
                        streams eval/tap events to the run directory's
                        ``events.jsonl`` and maintains ``manifest.json``.
                        ``None`` (or a disabled Telemetry) is provably free:
                        the engine takes the exact pre-telemetry code path
                        and chunk-cache keys/traces are unchanged.
    """
    strategy: Strategy
    eval_every: int = 20
    network: Optional[Any] = None
    checkpoint_dir: Optional[str] = None
    schedule: Optional[RoundSchedule] = None
    ledger: Optional[PrivacyLedger] = None
    faults: Optional[Any] = None
    checkpoint_keep: int = 0
    telemetry: Optional[Any] = None

    # whether the opt-in metrics tap is inserted INTO the traced round body
    # (io_callback). ShardedEngine keeps its shard_map trace tap-free and
    # streams the same events host-side from the stacked chunk outputs.
    _tap_in_jit = True

    def __post_init__(self):
        if self.schedule is None:
            self.schedule = FullParticipation()

    # ------------------------------------------------------- telemetry seams
    def _telemetry_on(self):
        tel = self.telemetry
        return tel if (tel is not None and tel.enabled) else None

    def _tap_traced(self) -> bool:
        """True when the in-jit tap is part of this engine's chunk trace."""
        tel = self._telemetry_on()
        return bool(tel is not None and tel.tap and self._tap_in_jit)

    # ------------------------------------------------------------------
    def _chunk_key(self, length: int, batch_size: Optional[int]) -> Tuple:
        """Global-cache key: everything that changes the traced computation.
        Strategy/schedule fingerprints carry cache_token, groups, lr, DP
        on/off, ... — σ is deliberately absent (runtime argument); the
        runtime-param *keys* are in (their presence gates noise ops)."""
        base = (self.strategy.fingerprint(), self.schedule.fingerprint(),
                length, batch_size,
                tuple(sorted(self.strategy.runtime_params())),
                None if self.faults is None else self.faults.fingerprint(),
                self._mesh_fingerprint())
        # the in-jit tap is part of the traced computation, so it is part of
        # the key — but ONLY when on: with telemetry off/absent the key is
        # byte-identical to the pre-telemetry key (the zero-overhead-off
        # contract the equivalence tier locks)
        return base + (("tap",) if self._tap_traced() else ())

    def _mesh_fingerprint(self) -> Tuple:
        return ()   # single-device loop; ShardedEngine adds (axis, n, M)

    def _chunk_fn(self, length: int, batch_size: Optional[int],
                  data: FederatedData):
        """Jitted scan over ``length`` rounds; the state carry is donated.
        Cached globally across Engine instances (see CHUNK_CACHE above)."""
        key_ = self._chunk_key(length, batch_size)
        fn = _cache_get(key_)
        if fn is not None:
            return fn
        schedule, strategy, faults = self.schedule, self.strategy, self.faults
        tap = None
        if self._tap_traced():
            from repro.obs.telemetry import tap_scan
            tap = tap_scan

        def run(state, phase_key, train_x, train_y, start, rt):
            CHUNK_STATS["traces"] += 1   # python body executes per trace only
            # M comes from the traced stacks: the cache key does not hold it
            body = schedule.round_body(strategy, batch_size,
                                       ResidentCtx(train_y.shape[0]))
            if faults is not None:
                from repro.resilience import wrap_round_body
                body = wrap_round_body(body, faults)
            with runtime_params(rt):
                def scan_body(state, r):
                    return body(state, r, phase_key, train_x, train_y)

                rs = start + jnp.arange(length)
                if tap is not None:
                    return tap(scan_body, state, rs, rt)
                return jax.lax.scan(scan_body, state, rs)

        fn = jax.jit(run, donate_argnums=0)
        _cache_put(key_, fn)
        return fn

    def run_rounds(self, state, data: FederatedData, phase_key, start: int,
                   stop: int, batch_size: Optional[int]):
        """Run rounds [start, stop) as one scanned chunk. Returns
        (state, metrics, aux) with metrics/aux stacked over the chunk's
        rounds; aux carries the (chunk, M) participation masks under a
        sampling schedule (empty dict otherwise). The chunk is one
        ``engine.run_rounds`` span."""
        if stop <= start:
            return state, {}, {}
        with self._chunk_span(start, stop):
            return self._run_chunk(state, data, phase_key, start, stop,
                                   batch_size)

    def _run_chunk(self, state, data: FederatedData, phase_key, start: int,
                   stop: int, batch_size: Optional[int]):
        fn = self._build_chunk(self._chunk_fn, stop - start, batch_size, data)
        train_x, train_y = self._train_arrays(data)
        rt = {k: jnp.asarray(v, jnp.float32)
              for k, v in self.strategy.runtime_params().items()}
        carry = state if self.faults is None else (state, self._fault_state)
        carry, (metrics, aux) = self._dispatch_chunk(
            fn, (carry, phase_key, train_x, train_y,
                 jnp.asarray(start, jnp.int32), rt),
            start, stop, rt)
        if self.faults is None:
            state = carry
        else:
            state, self._fault_state = carry
        return state, metrics, aux

    # ----------------------------------------------------- spans and dispatch
    def _chunk_span(self, start: int, stop: int):
        """The chunk's ``engine.run_rounds`` span: with telemetry on, the
        span event carries the trace-vs-execute split, the chunk's compiles
        and the optional Nth-chunk profiler capture; off, it is one
        inactive TraceMe unless a profiler is running."""
        tel = self._telemetry_on()
        if tel is None:
            return span("engine.run_rounds", chunk=True)
        return tel.chunk_span(start=int(start), stop=int(stop))

    def _build_chunk(self, builder, *args):
        """Chunk-cache key and lookup (or build): the
        ``engine.chunk_lookup`` span. A build traces nothing yet; the trace
        lands in the first dispatch."""
        with span("engine.chunk_lookup"):
            return builder(*args)

    def _dispatch_chunk(self, fn, args, start: int, stop: int, rt=None):
        """Execute one compiled chunk: the ``engine.dispatch`` span. Its
        first dispatch records the chunk's traced signature for the op ->
        layer table (``repro.obs.layers``); later ones cost a dict hit.
        Telemetry on: the call blocks inside the span, the tap's
        io_callbacks route to this run's sink, and engines whose trace is
        tap-free (sharded) stream the per-round events from the stacked
        outputs instead."""
        tel = self._telemetry_on()
        if tel is None:
            with span("engine.dispatch"):
                note_dispatch(fn, args)
                return fn(*args)
        with tel.activate(), span("engine.dispatch", tel):
            note_dispatch(fn, args)
            out = fn(*args)
            jax.block_until_ready(out)
            if tel.tap and self._tap_in_jit:
                # callbacks ride XLA's host-callback thread: drain them while
                # this run's sink is still the active one
                jax.effects_barrier()
        if tel.tap and not self._tap_in_jit:
            _, (metrics, aux) = out
            tel.emit_tap_stacked(int(start), int(stop) - int(start),
                                 metrics, aux, rt)
        return out

    # ------------------------------------------------- sharded-engine seams
    def _train_arrays(self, data: FederatedData):
        """Training stacks as the chunk consumes them (padded under a client
        mesh)."""
        return data.train_x, data.train_y

    def _prepare_state(self, state, data: FederatedData):
        """Engine-internal state representation (client-padded + mesh-sharded
        under ShardedEngine; identity here)."""
        return state

    def _finalize_state(self, state):
        """Back to the strategy-visible representation (unpad) for evaluate,
        checkpointing, and the value ``fit`` returns."""
        return state

    # ------------------------------------------------- checkpointing seams
    # (PagedEngine splits the save across a plain npz for the non-paged
    #  remainder plus an incremental dirty-row population chain, and resume
    #  must land on a step whose WHOLE set verifies — hence three seams.)

    def _latest_resume_step(self) -> Optional[int]:
        from repro.checkpoint import latest_step
        return latest_step(self.checkpoint_dir)

    def _restore_for_resume(self, state, data: FederatedData,
                            resume_step: int):
        """Restore ``resume_step`` into the engine-internal representation.
        Returns (state, resume_step, history-dict-or-None)."""
        from repro.checkpoint import (load_checkpoint_metadata,
                                      restore_checkpoint)
        saved, resume_step = restore_checkpoint(
            self.checkpoint_dir,
            self.strategy.state_to_save(self._finalize_state(state)),
            resume_step)
        state = self._prepare_state(saved, data)
        meta = load_checkpoint_metadata(self.checkpoint_dir, resume_step)
        return state, resume_step, (meta or {}).get("history")

    def _save_checkpoint(self, ev: int, state, history: "History") -> None:
        from repro.checkpoint import save_checkpoint
        save_checkpoint(self.checkpoint_dir, ev,
                        self.strategy.state_to_save(
                            self._finalize_state(state)),
                        metadata={"history": {
                            "rounds": history.rounds,
                            "accuracy": history.accuracy,
                            "metrics": history.metrics}},
                        keep_last=self.checkpoint_keep)

    # ------------------------------------------------------------------
    def fit(self, data: FederatedData, *, rounds: int, key,
            batch_size: Optional[int] = None, start_round: int = 0,
            state=None, evaluate: bool = True, history: Optional[History] = None,
            resume: bool = False, target_epsilon: Optional[float] = None):
        """Run one phase of training: rounds [start_round, rounds).

        ``state=None`` initializes via the strategy. With ``evaluate=False``
        the phase runs as a single chunk with no eval (P4's bootstrap).

        ``target_epsilon`` requests a privacy budget instead of a noise
        multiplier: the ledger calibrates σ for the phase's rounds at the
        schedule's effective sampling rate and installs it on the strategy
        (``set_sigma``) before any chunk is traced.
        """
        strategy = self.strategy
        init_key, phase_key = jax.random.split(jax.random.fold_in(key, 0x9e37))
        history = history if history is not None else History()
        # the fault chains' time origin is the phase's first round as CALLED —
        # a resumed fit passes the same start_round, so host replay rejoins
        # the exact trajectory the killed run was on
        self._fault_origin = start_round

        # resolve the resume point BEFORE calibration and init: calibrating
        # with the pre-resume start_round would size σ for rounds that will
        # never run (the old double-advance hazard), and strategies whose
        # init consumes σ (e.g. DP-DSGT's noised tracker bootstrap) must see
        # the calibrated value
        resume_step = None
        if resume and self.checkpoint_dir:
            resume_step = self._latest_resume_step()
        if resume_step is not None and self.ledger is not None:
            # the rounds skipped by the resume were spent by the pre-restart
            # run — an accountant that forgot them would under-report the
            # release's true (ε, δ)
            self.ledger.advance(resume_step + 1 - start_round)
        if target_epsilon is not None:
            if self.ledger is None:
                raise ValueError("target_epsilon requires a PrivacyLedger")
            remaining = rounds - (resume_step + 1 if resume_step is not None
                                  else start_round)
            # composes on the ledger's accumulated spend (incl. the resumed
            # rounds just advanced): past + future lands on the target
            strategy.set_sigma(self.ledger.calibrate(target_epsilon,
                                                     remaining))

        if state is None:
            state = strategy.init(init_key, data, batch_size)
        state = self._prepare_state(state, data)
        if resume_step is not None:
            state, resume_step, h = self._restore_for_resume(state, data,
                                                             resume_step)
            start_round = resume_step + 1
            # the sidecar carries the killed run's History: restoring it makes
            # the resumed record bit-exact with an uninterrupted run (floats
            # round-trip exactly through JSON's shortest-repr)
            if h and not history.rounds:
                history.rounds[:] = [int(x) for x in h.get("rounds", [])]
                history.accuracy[:] = [float(x) for x in h.get("accuracy", [])]
                history.metrics.clear()
                history.metrics.update({k: [float(x) for x in v]
                                        for k, v in h.get("metrics", {}).items()})

        self._fault_state = None
        if self.faults is not None:
            # fast-forward the fault chains to start_round by eager replay
            # from the phase origin (bit-identical to the traced transitions)
            from repro.resilience import fault_state_at
            self._fault_state = fault_state_at(self.faults, phase_key,
                                               self._fault_origin, start_round)

        tel = self._telemetry_on()
        if tel is not None:
            tel.begin_phase(self._phase_info(rounds, start_round, batch_size))

        boundaries = (eval_rounds(start_round, rounds, self.eval_every)
                      if evaluate else [])
        cursor = start_round
        for ev in boundaries:
            state, metrics, aux = self.run_rounds(state, data, phase_key,
                                                  cursor, ev + 1, batch_size)
            self._log_network(state, cursor, ev, aux.get("participation"),
                              phase_key)
            if self.ledger is not None:
                self.ledger.advance(ev + 1 - cursor)
            cursor = ev + 1
            acc = strategy.evaluate(self._finalize_state(state), data.test_x,
                                    data.test_y)
            chunk_means = {k: jnp.mean(v) for k, v in (metrics or {}).items()}
            if "participation" in aux:
                chunk_means["participation_rate"] = jnp.mean(
                    aux["participation"])
            for k, v in (aux or {}).items():
                if k.startswith("fault_"):
                    chunk_means[k] = jnp.mean(v)
            if self.ledger is not None:
                chunk_means.update(self.ledger.metrics())
            history.record(ev, jnp.mean(acc), chunk_means)
            if tel is not None:
                # copied from the History entry AFTER recording, so the
                # JSONL trajectory matches the returned History exactly
                tel.eval_event(ev, history.accuracy[-1],
                               {k: v[-1] for k, v in history.metrics.items()})
            if self.checkpoint_dir:
                self._save_checkpoint(ev, state, history)
        if cursor < rounds:  # tail (or the whole phase when evaluate=False)
            state, _, aux = self.run_rounds(state, data, phase_key, cursor,
                                            rounds, batch_size)
            self._log_network(state, cursor, rounds - 1,
                              aux.get("participation"), phase_key)
            if self.ledger is not None:
                self.ledger.advance(rounds - cursor)
        if tel is not None:
            tel.end_phase()
        return self._finalize_state(state), history

    def _phase_info(self, rounds: int, start_round: int,
                    batch_size: Optional[int]) -> Dict[str, Any]:
        """The run manifest's identity record for one ``fit`` phase."""
        import hashlib

        def fp(x):
            s = str(x)
            return {"sha1": hashlib.sha1(s.encode()).hexdigest()[:12],
                    "repr": s[:2000]}

        info = {"engine": type(self).__name__,
                "strategy": type(self.strategy).__name__,
                "schedule": type(self.schedule).__name__,
                "rounds": int(rounds), "start_round": int(start_round),
                "batch_size": None if batch_size is None else int(batch_size),
                "eval_every": int(self.eval_every),
                "mesh": str(self._mesh_fingerprint()),
                "strategy_fingerprint": fp(self.strategy.fingerprint()),
                "schedule_fingerprint": fp(self.schedule.fingerprint()),
                "faults": (None if self.faults is None
                           else str(self.faults.fingerprint()))}
        topo = getattr(self.strategy, "topology", None)
        if topo is not None and hasattr(topo, "fingerprint"):
            info["topology_fingerprint"] = fp(topo.fingerprint())
        return info

    # ------------------------------------------------------------------
    def _log_network(self, state, first_round: int, last_round: int,
                     masks=None, phase_key=None) -> None:
        if self.network is None:
            return
        frs = None
        if self.faults is not None:
            # re-derive the chunk's exact correlated realizations host-side,
            # the same way host_fault_masks re-derives the i.i.d. ones
            from repro.resilience import host_realizations
            frs = host_realizations(self.faults, phase_key,
                                    self._fault_origin, first_round,
                                    last_round + 1)
        masks = None if masks is None else np.asarray(masks)
        for i, r in enumerate(range(first_round, last_round + 1)):
            mask = None if masks is None else masks[i]
            self.strategy.log_communication(
                self.network, state, r, mask=mask, phase_key=phase_key,
                faults=None if frs is None else frs[i])


# ---------------------------------------------------------------------------
# LM-scale step loop (the launch/train.py --p4 driver)
# ---------------------------------------------------------------------------

def make_scan_steps(step_fn, make_batch, length: int):
    """Chunk ``length`` LM training steps under one jitted ``lax.scan``.

    ``make_batch(key, i)`` must build the step's batch *inside* the trace
    (e.g. ``jax.random``-drawn synthetic tokens) so the loop never touches
    the host; the (params, opt_states) carry is donated.
    """
    def run(params, opt_states, key, start):
        def body(carry, i):
            params, opt_states = carry
            k = jax.random.fold_in(key, i)
            batch = make_batch(jax.random.fold_in(k, 0), i)
            params, opt_states, metrics = step_fn(
                params, opt_states, batch, jax.random.fold_in(k, 1))
            return (params, opt_states), metrics["loss"]

        (params, opt_states), losses = jax.lax.scan(
            body, (params, opt_states), start + jnp.arange(length))
        return params, opt_states, losses

    return jax.jit(run, donate_argnums=(0, 1))
