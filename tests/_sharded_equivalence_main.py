"""Sharded-vs-single-device equivalence scenarios, executed as a subprocess
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` by
``tests/test_sharded_engine.py`` (the flag must be set before the first jax
init, hence the process boundary — same recipe as the mini dry-run).

Prints ONE JSON object: scenario name -> equivalence record. The host-side
tests assert on the records, so a failure names the exact scenario."""
from __future__ import annotations

import json
import sys


def _leaves(tree):
    import jax
    import numpy as np
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(tree)]


def tree_bit_equal(a, b) -> bool:
    import numpy as np
    return all(np.array_equal(x, y) for x, y in zip(_leaves(a), _leaves(b)))


def tree_maxdiff(a, b) -> float:
    import numpy as np
    return float(max(np.max(np.abs(x.astype(np.float64) - y.astype(np.float64)))
                     for x, y in zip(_leaves(a), _leaves(b))))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import topology as topo_lib
    from repro.baselines.dp_dsgt import DPDSGTStrategy
    from repro.baselines.fedavg import FedAvgStrategy
    from repro.baselines.local import LocalStrategy
    from repro.baselines.proxyfl import ProxyFLStrategy
    from repro.baselines.scaffold import ScaffoldStrategy
    from repro.config import DPConfig, P4Config, RunConfig, TrainConfig
    from repro.core.p2p import P2PNetwork
    from repro.core.p4 import P4Strategy, P4Trainer
    from repro.engine import (AsyncStaleness, ClientSampling, ClientShardCtx,
                              Engine, FederatedData, ShardedEngine)
    from repro.launch.mesh import make_client_mesh
    from repro.topology.mixing import (edges_shard_resident, make_plan,
                                       mix_stats_snapshot, reset_mix_stats)

    assert len(jax.devices()) == 8, jax.devices()
    mesh8 = make_client_mesh()
    results = {"devices": len(jax.devices())}

    rng = np.random.default_rng(0)
    M, feat, classes, n = 8, 12, 3, 32
    protos = rng.normal(size=(classes, feat)).astype(np.float32) * 3
    ys = rng.integers(0, classes, size=(M, n))
    xs = protos[ys] + rng.normal(size=(M, n, feat)).astype(np.float32) * 0.4
    X, Y = xs, ys.astype(np.int32)
    data8 = FederatedData(X, Y, jnp.asarray(X), jnp.asarray(Y))
    data6 = FederatedData(X[:6], Y[:6], jnp.asarray(X[:6]), jnp.asarray(Y[:6]))
    key = jax.random.PRNGKey(0)

    def compare(name, mk_strategy, schedule=None, data=data8, rounds=8,
                batch=8, mesh=mesh8, faults=None):
        mk_sched = schedule if schedule is not None else (lambda: None)
        mk_faults = faults if faults is not None else (lambda: None)
        st1, h1 = Engine(mk_strategy(), eval_every=3, schedule=mk_sched(),
                         faults=mk_faults()).fit(
            data, rounds=rounds, key=key, batch_size=batch)
        # collective probe: trace-time counts over the sharded run only (the
        # single-device mix never touches MIX_STATS). Counts are per chunk
        # trace, so "0 gathers" is asserted as all_gathers == 0 outright.
        reset_mix_stats()
        st2, h2 = ShardedEngine(mk_strategy(), eval_every=3, mesh=mesh,
                                schedule=mk_sched(), faults=mk_faults()).fit(
            data, rounds=rounds, key=key, batch_size=batch)
        results[name] = {
            "mix_stats": mix_stats_snapshot(),
            "rounds_equal": h1.rounds == h2.rounds,
            "accuracy_bit_equal": h1.accuracy == h2.accuracy,
            "accuracy_maxdiff": float(max(abs(a - b) for a, b in
                                          zip(h1.accuracy, h2.accuracy))),
            "metrics_maxdiff": float(max(
                (max(abs(p - q) for p, q in zip(v, h2.metrics[k]))
                 for k, v in h1.metrics.items()), default=0.0)),
            "state_bit_equal": tree_bit_equal(st1, st2),
            "state_maxdiff": tree_maxdiff(st1, st2),
        }

    dp = DPConfig(clip_norm=1.0)
    compare("local_full", lambda: LocalStrategy(
        feat_dim=feat, num_classes=classes, lr=0.5, dp_cfg=dp, sigma=0.7))
    compare("local_full_uneven", lambda: LocalStrategy(
        feat_dim=feat, num_classes=classes, lr=0.5, dp_cfg=dp, sigma=0.7),
        data=data6)
    compare("local_sampling_uneven", lambda: LocalStrategy(
        feat_dim=feat, num_classes=classes, lr=0.5),
        schedule=lambda: ClientSampling(q=0.5), data=data6)

    # the gather reduction keeps the strict bit-exact contract; the default
    # psum tree-reduction is verified separately (tolerance + vs-gather)
    compare("fedavg_full", lambda: FedAvgStrategy(
        feat_dim=feat, num_classes=classes, lr=0.5, clip=1.0, sigma=0.5,
        user_ratio=0.8, reduce="gather"))
    compare("fedavg_sampling", lambda: FedAvgStrategy(
        feat_dim=feat, num_classes=classes, lr=0.5, clip=1.0, sigma=0.4,
        reduce="gather"),
        schedule=lambda: ClientSampling(q=0.6))
    compare("fedavg_async0", lambda: FedAvgStrategy(
        feat_dim=feat, num_classes=classes, lr=0.5, clip=1.0, sigma=0.4,
        reduce="gather"),
        schedule=lambda: AsyncStaleness(staleness=0))

    # psum-tree cohort reduction (the default): bit-close to single-device
    # and to the gather path on the same mesh
    compare("fedavg_psum_full", lambda: FedAvgStrategy(
        feat_dim=feat, num_classes=classes, lr=0.5, clip=1.0, sigma=0.5,
        user_ratio=0.8))
    compare("fedavg_psum_sampling", lambda: FedAvgStrategy(
        feat_dim=feat, num_classes=classes, lr=0.5, clip=1.0, sigma=0.4),
        schedule=lambda: ClientSampling(q=0.6))

    def fedavg_sharded(reduce):
        strat = FedAvgStrategy(feat_dim=feat, num_classes=classes, lr=0.5,
                               clip=1.0, sigma=0.5, user_ratio=0.8,
                               reduce=reduce)
        return ShardedEngine(strat, eval_every=3, mesh=mesh8).fit(
            data8, rounds=8, key=key, batch_size=8)

    st_p, h_p = fedavg_sharded("psum")
    st_g, h_g = fedavg_sharded("gather")
    results["fedavg_psum_vs_gather"] = {
        "rounds_equal": h_p.rounds == h_g.rounds,
        "accuracy_maxdiff": float(max(abs(a - b) for a, b in
                                      zip(h_p.accuracy, h_g.accuracy))),
        "state_maxdiff": tree_maxdiff(st_p, st_g),
    }

    # ---------------- scaffold / proxyfl: sharded-hook ports ----------------
    compare("scaffold_full", lambda: ScaffoldStrategy(
        feat_dim=feat, num_classes=classes, lr=0.5, clip=1.0, sigma=0.4))
    compare("scaffold_sampling", lambda: ScaffoldStrategy(
        feat_dim=feat, num_classes=classes, lr=0.5, clip=1.0, sigma=0.4),
        schedule=lambda: ClientSampling(q=0.6))
    compare("scaffold_uneven", lambda: ScaffoldStrategy(
        feat_dim=feat, num_classes=classes, lr=0.5, clip=1.0, sigma=0.4),
        data=data6)
    compare("proxyfl_full", lambda: ProxyFLStrategy(
        feat_dim=feat, num_classes=classes, lr=0.5, clip=1.0, sigma=0.4))
    compare("proxyfl_uneven", lambda: ProxyFLStrategy(
        feat_dim=feat, num_classes=classes, lr=0.5, clip=1.0, sigma=0.4),
        data=data6)

    compare("dsgt_full", lambda: DPDSGTStrategy(
        feat_dim=feat, num_classes=classes, lr=0.3, clip=1.0, sigma=0.5))
    compare("dsgt_full_uneven", lambda: DPDSGTStrategy(
        feat_dim=feat, num_classes=classes, lr=0.3, clip=1.0, sigma=0.5),
        data=data6)
    compare("dsgt_sampling", lambda: DPDSGTStrategy(
        feat_dim=feat, num_classes=classes, lr=0.3, clip=1.0, sigma=0.4),
        schedule=lambda: ClientSampling(q=0.5))
    compare("dsgt_async2", lambda: DPDSGTStrategy(
        feat_dim=feat, num_classes=classes, lr=0.3, clip=1.0, sigma=0.4),
        schedule=lambda: AsyncStaleness(staleness=2))

    # -------------- topology subsystem: non-ring graphs + faults ------------
    # ISSUE 5 acceptance: a non-ring topology (4-regular circulant expander,
    # edges cross every slice boundary → the gather mixing path) and a faulty
    # run (drop + churn drawn in-jit, replicated across slices)
    expander = topo_lib.k_regular(M, 4)
    compare("dsgt_topology_expander", lambda: DPDSGTStrategy(
        feat_dim=feat, num_classes=classes, lr=0.3, clip=1.0, sigma=0.5,
        topology=expander))
    compare("dsgt_topology_faulty", lambda: DPDSGTStrategy(
        feat_dim=feat, num_classes=classes, lr=0.3, clip=1.0, sigma=0.5,
        topology=expander.with_faults(0.25, 0.1)))
    compare("dsgt_gossip_sequence", lambda: DPDSGTStrategy(
        feat_dim=feat, num_classes=classes, lr=0.3, clip=1.0, sigma=0.5,
        topology=topo_lib.gossip_matchings(M, period=4, seed=0)))

    # ISSUE 7: banded topologies must stay gather-free on the sharded path —
    # keep-masked / i.i.d.-faulty rings route through the halo exchange
    # (dropped mass folds into the diagonal locally, no collective), and the
    # torus rides the general bounded-bandwidth halo schedule
    compare("dsgt_ring_faulty", lambda: DPDSGTStrategy(
        feat_dim=feat, num_classes=classes, lr=0.3, clip=1.0, sigma=0.5,
        topology=topo_lib.ring(M).with_faults(0.25, 0.1)))
    from repro.resilience import (FaultModel, gilbert_elliott_rates,
                                  make_fault_process)
    ge_fail, ge_repair = gilbert_elliott_rates(0.3, 3.0)
    compare("dsgt_ring_burst", lambda: DPDSGTStrategy(
        feat_dim=feat, num_classes=classes, lr=0.3, clip=1.0, sigma=0.5,
        topology=topo_lib.ring(M)),
        faults=lambda: make_fault_process(
            FaultModel(link_fail=ge_fail, link_repair=ge_repair), M))
    compare("dsgt_torus", lambda: DPDSGTStrategy(
        feat_dim=feat, num_classes=classes, lr=0.3, clip=1.0, sigma=0.5,
        topology=topo_lib.torus(4, 2)))

    # ISSUE 9: learned directed graphs mix via push-sum — the weight scalar
    # rides the x mix as a joint leaf, so the sharded lowering must stay
    # equivalent through the same halo/gather path selection. One static
    # estimate, one faulted (the sender-side diagonal fold), and one
    # time-varying two-estimate window.
    lrn_rng = np.random.default_rng(7)
    learner = topo_lib.GraphLearner(M=M, k=3, sigma_dist=0.5, seed=7)
    learned_a = learner.estimate(
        lrn_rng.normal(size=(M, 24)).astype(np.float32))
    learner.estimate(lrn_rng.normal(size=(M, 24)).astype(np.float32))
    learned_tv = learner.current(window=2)
    assert make_plan(learned_a).push_sum
    compare("dsgt_learned_pushsum", lambda: DPDSGTStrategy(
        feat_dim=feat, num_classes=classes, lr=0.3, clip=1.0, sigma=0.5,
        topology=learned_a))
    compare("dsgt_learned_faulty", lambda: DPDSGTStrategy(
        feat_dim=feat, num_classes=classes, lr=0.3, clip=1.0, sigma=0.5,
        topology=learned_a.with_faults(0.25, 0.1)))
    compare("dsgt_learned_timevarying", lambda: DPDSGTStrategy(
        feat_dim=feat, num_classes=classes, lr=0.3, clip=1.0, sigma=0.5,
        topology=learned_tv))

    # shard-resident topology on a 2-slice mesh: the mix needs no collective
    mesh2_t = make_client_mesh(2)
    resident_topo = topo_lib.group_clustered([[0, 1, 2, 3], [4, 5, 6, 7]], M,
                                             bridge=False)
    results["topology_resident_layout"] = {
        "resident_on_2": edges_shard_resident(
            make_plan(resident_topo), ClientShardCtx(mesh2_t, "clients", M)),
        "resident_on_8": edges_shard_resident(
            make_plan(resident_topo), ClientShardCtx(mesh8, "clients", M)),
    }
    compare("dsgt_topology_resident", lambda: DPDSGTStrategy(
        feat_dim=feat, num_classes=classes, lr=0.3, clip=1.0, sigma=0.5,
        topology=resident_topo), mesh=mesh2_t)
    compare("dsgt_topology_resident_faulty", lambda: DPDSGTStrategy(
        feat_dim=feat, num_classes=classes, lr=0.3, clip=1.0, sigma=0.5,
        topology=resident_topo.with_faults(0.3, 0.0)), mesh=mesh2_t)

    # ---------------- P4: strategy-level (fixed groups) across schedules ----
    def p4_cfg(rounds=8):
        return RunConfig(dp=DPConfig(epsilon=15.0, rounds=rounds,
                                     sample_rate=0.5),
                         p4=P4Config(group_size=4, sample_peers=7),
                         train=TrainConfig(learning_rate=0.5))

    def mk_p4(groups, topology=None):
        def mk():
            strat = P4Strategy(trainer=P4Trainer(feat_dim=feat,
                                                 num_classes=classes,
                                                 cfg=p4_cfg()))
            strat.set_groups([list(g) for g in groups], M)
            if topology is not None:
                strat.set_topology(topology)
            return strat
        return mk

    spanning = [[0, 2, 4, 6], [1, 3, 5, 7]]   # every group spans 4 slices
    compare("p4_full_gather", mk_p4(spanning))
    compare("p4_sampling", mk_p4(spanning),
            schedule=lambda: ClientSampling(q=0.5))
    compare("p4_async1", mk_p4(spanning),
            schedule=lambda: AsyncStaleness(staleness=1))

    # pod-resident groups on a 2-slice mesh: aggregation needs no collective
    mesh2 = make_client_mesh(2)
    resident = [[0, 1, 2, 3], [4, 5, 6, 7]]
    probe = mk_p4(resident)()
    ctx2 = ClientShardCtx(mesh2, "clients", M)
    results["p4_resident_layout"] = {
        "resident_on_2": probe._groups_shard_resident(ctx2),
        "resident_on_8": probe._groups_shard_resident(
            ClientShardCtx(mesh8, "clients", M)),
    }
    compare("p4_full_resident", mk_p4(resident), mesh=mesh2)
    compare("p4_sampling_resident", mk_p4(resident), mesh=mesh2,
            schedule=lambda: ClientSampling(q=0.5))

    # fault-injected P4: member↔aggregator links drop in-jit; the resident
    # layout slices the replicated fault mask, the spanning one gathers
    p4_fault_topo = topo_lib.group_clustered(
        [list(g) for g in resident], M).with_faults(0.3, 0.1)
    compare("p4_faulty_resident", mk_p4(resident, p4_fault_topo), mesh=mesh2)
    compare("p4_faulty_gather", mk_p4(spanning, topo_lib.group_clustered(
        [list(g) for g in spanning], M).with_faults(0.3, 0.1)))

    # -------- resilience: correlated fault regimes, sharded ≡ single --------
    # the FaultState carry is replicated across slices (every shard steps the
    # identical Markov transition from the replicated phase key), so every
    # regime must realize the same masks on both layouts
    regimes = {
        "burst": FaultModel(link_fail=ge_fail, link_repair=ge_repair),
        "churn": FaultModel(node_fail=0.25, node_repair=0.4),
        "partition": FaultModel(partition_prob=0.25, partition_repair=0.3),
    }
    for rname, fm in regimes.items():
        compare(f"dsgt_fault_{rname}", lambda: DPDSGTStrategy(
            feat_dim=feat, num_classes=classes, lr=0.3, clip=1.0, sigma=0.5,
            topology=expander),
            faults=lambda: make_fault_process(fm, M))

    straggler = FaultModel(slow_enter=0.3, slow_exit=0.5)
    compare("fedavg_fault_straggler", lambda: FedAvgStrategy(
        feat_dim=feat, num_classes=classes, lr=0.5, clip=1.0, sigma=0.4,
        reduce="gather"),
        schedule=lambda: AsyncStaleness(staleness=1),
        faults=lambda: make_fault_process(straggler, M))
    compare("p4_fault_straggler", mk_p4(spanning),
            schedule=lambda: AsyncStaleness(staleness=1),
            faults=lambda: make_fault_process(straggler, M))

    # failover under combined faults + quorum, on the pod-resident layout
    # (the sliced reach mask) and the gather layout
    failover_fm = FaultModel(link_fail=ge_fail, link_repair=ge_repair,
                             node_fail=0.3, node_repair=0.4, quorum=0.5)
    compare("p4_fault_failover_resident", mk_p4(resident), mesh=mesh2,
            faults=lambda: make_fault_process(failover_fm, M))
    compare("p4_fault_failover_gather", mk_p4(spanning),
            faults=lambda: make_fault_process(failover_fm, M))

    # -------- paged cohorts: PagedEngine ≡ resident Engine (ISSUE 8) -------
    # the host-resident population with paged cohorts must be bit-exact with
    # the resident engine — state AND History — across every strategy ×
    # schedule, including uneven cohort sizes (M=6, fixed-k and Bernoulli
    # draws) and a correlated fault regime. P4's train-loss means under
    # sampling are the one documented difference (cohort mean vs the
    # resident's full-M mean) and are excluded, not asserted loosely.
    from repro.engine.population import PagedEngine

    def compare_paged(name, mk_strategy, schedule=None, data=data8, rounds=8,
                      batch=8, faults=None, mesh=None, exclude_metrics=()):
        mk_sched = schedule if schedule is not None else (lambda: None)
        mk_faults = faults if faults is not None else (lambda: None)
        st1, h1 = Engine(mk_strategy(), eval_every=3, schedule=mk_sched(),
                         faults=mk_faults()).fit(
            data, rounds=rounds, key=key, batch_size=batch)
        st2, h2 = PagedEngine(mk_strategy(), eval_every=3,
                              schedule=mk_sched(), faults=mk_faults(),
                              mesh=mesh).fit(
            data, rounds=rounds, key=key, batch_size=batch)
        excl = set(exclude_metrics)
        results[name] = {
            "rounds_equal": h1.rounds == h2.rounds,
            "accuracy_bit_equal": h1.accuracy == h2.accuracy,
            "accuracy_maxdiff": float(max(abs(a - b) for a, b in
                                          zip(h1.accuracy, h2.accuracy))),
            "metrics_bit_equal": all(v == h2.metrics.get(k)
                                     for k, v in h1.metrics.items()
                                     if k not in excl),
            "excluded_maxdiff": float(max(
                (max(abs(p - q) for p, q in zip(h1.metrics[k], h2.metrics[k]))
                 for k in excl), default=0.0)),
            "state_bit_equal": tree_bit_equal(st1, st2),
            "state_maxdiff": tree_maxdiff(st1, st2),
        }

    def mk_fedavg(sigma=0.4):
        return lambda: FedAvgStrategy(feat_dim=feat, num_classes=classes,
                                      lr=0.5, clip=1.0, sigma=sigma)

    def mk_dsgt(topology=None):
        return lambda: DPDSGTStrategy(feat_dim=feat, num_classes=classes,
                                      lr=0.3, clip=1.0, sigma=0.4,
                                      topology=topology)

    compare_paged("paged_fedavg_full", mk_fedavg(0.5))
    compare_paged("paged_fedavg_sampling_uneven", mk_fedavg(),
                  schedule=lambda: ClientSampling(q=0.6), data=data6)
    compare_paged("paged_fedavg_bernoulli", mk_fedavg(),
                  schedule=lambda: ClientSampling(q=0.5, mode="bernoulli"))
    compare_paged("paged_fedavg_async0", mk_fedavg(),
                  schedule=lambda: AsyncStaleness(staleness=0))
    compare_paged("paged_dsgt_full", mk_dsgt())
    compare_paged("paged_dsgt_sampling", mk_dsgt(),
                  schedule=lambda: ClientSampling(q=0.5))
    compare_paged("paged_dsgt_sampling_uneven", mk_dsgt(),
                  schedule=lambda: ClientSampling(q=0.5), data=data6)
    compare_paged("paged_dsgt_async2", mk_dsgt(),
                  schedule=lambda: AsyncStaleness(staleness=2))
    # non-ring graph: the cohort closure pages in every in-neighbor and the
    # paged mix resolves reads through the slot map's general path
    compare_paged("paged_dsgt_expander_sampling", mk_dsgt(expander),
                  schedule=lambda: ClientSampling(q=0.5))
    compare_paged("paged_p4_full", mk_p4(spanning))
    compare_paged("paged_p4_sampling", mk_p4(spanning),
                  schedule=lambda: ClientSampling(q=0.5),
                  exclude_metrics=("private_loss", "proxy_loss"))
    compare_paged("paged_p4_async1", mk_p4(spanning),
                  schedule=lambda: AsyncStaleness(staleness=1))
    # correlated fault regime: the fault carry is host-replicated and full-M,
    # the planned cohort is a superset of realized participants (faults only
    # remove clients), so the paged run realizes the identical masks
    compare_paged("paged_fedavg_sampling_faulty", mk_fedavg(),
                  schedule=lambda: ClientSampling(q=0.6),
                  faults=lambda: make_fault_process(
                      FaultModel(node_fail=0.25, node_repair=0.4), M))
    # cohort axis sharded over the clients mesh (GSPMD partitioning of the
    # paged chunk): numerically tight, not bit-exact — partitioned
    # reductions reassociate
    compare_paged("paged_mesh_fedavg_sampling", mk_fedavg(),
                  schedule=lambda: ClientSampling(q=0.6), mesh=mesh8)

    # -------- telemetry: off ≡ never-constructed, tap-on ≡ untapped ---------
    # ISSUE 10 zero-overhead-off contract on the sharded path: a disabled
    # Telemetry must leave the chunk-cache key and every result bit-exact;
    # an ENABLED tap must too (the sharded trace stays tap-free — per-round
    # events stream host-side from the stacked chunk outputs)
    import tempfile

    from repro.obs import Telemetry

    def mk_tel_strat():
        return LocalStrategy(feat_dim=feat, num_classes=classes, lr=0.5,
                             dp_cfg=dp, sigma=0.7)

    st_ref, h_ref = Engine(mk_tel_strat(), eval_every=3).fit(
        data8, rounds=8, key=key, batch_size=8)
    eng_plain = ShardedEngine(mk_tel_strat(), eval_every=3, mesh=mesh8)
    eng_off = ShardedEngine(mk_tel_strat(), eval_every=3, mesh=mesh8,
                            telemetry=Telemetry(None, tap=True))
    tap_dir = tempfile.mkdtemp(prefix="obs_equiv_")
    tel_on = Telemetry(tap_dir, tap=True)
    eng_on = ShardedEngine(mk_tel_strat(), eval_every=3, mesh=mesh8,
                           telemetry=tel_on)
    keys_equal = (eng_plain._chunk_key(8, 8) == eng_off._chunk_key(8, 8)
                  == eng_on._chunk_key(8, 8))
    st_off, h_off = eng_off.fit(data8, rounds=8, key=key, batch_size=8)
    st_on, h_on = eng_on.fit(data8, rounds=8, key=key, batch_size=8)
    tel_on.close()
    with open(tel_on.events_path) as f:
        tap_rounds = sorted(json.loads(line)["round"] for line in f
                            if line.strip()
                            and json.loads(line).get("type") == "tap")
    results["telemetry_off_sharded"] = {
        "chunk_key_unchanged": bool(keys_equal),
        "rounds_equal": h_ref.rounds == h_off.rounds == h_on.rounds,
        "accuracy_bit_equal": (h_ref.accuracy == h_off.accuracy
                               == h_on.accuracy),
        "state_bit_equal": (tree_bit_equal(st_ref, st_off)
                            and tree_bit_equal(st_ref, st_on)),
        "state_maxdiff": max(tree_maxdiff(st_ref, st_off),
                             tree_maxdiff(st_ref, st_on)),
        "tap_rounds": tap_rounds,
    }

    # ---------------- P4 end-to-end: bootstrap -> grouping -> co-train ------
    protos2 = rng.normal(size=(2, 4, 20)).astype(np.float32) * 2
    protos2[0, :, 10:] = 0
    protos2[1, :, :10] = 0
    e_xs, e_ys = [], []
    for c in range(M):
        y = rng.integers(0, 4, 48)
        e_xs.append(protos2[c % 2, y]
                    + rng.normal(size=(48, 20)).astype(np.float32) * 0.5)
        e_ys.append(y)
    EX = np.stack(e_xs)
    EY = np.stack(e_ys).astype(np.int32)

    def p4_e2e(mesh):
        tr = P4Trainer(feat_dim=20, num_classes=4, cfg=RunConfig(
            dp=DPConfig(epsilon=15.0, rounds=12, sample_rate=0.5),
            p4=P4Config(group_size=4, sample_peers=7),
            train=TrainConfig(learning_rate=0.5)))
        st, groups, hist = tr.fit(EX, EY, jnp.asarray(EX), jnp.asarray(EY),
                                  rounds=12, eval_every=5, mesh=mesh)
        return st, groups, hist

    st1, g1, h1 = p4_e2e(None)
    st2, g2, h2 = p4_e2e(mesh8)
    results["p4_end_to_end"] = {
        "groups_equal": g1 == g2,
        "rounds_equal": h1.rounds == h2.rounds,
        "accuracy_bit_equal": h1.accuracy == h2.accuracy,
        "state_bit_equal": tree_bit_equal(st1, st2),
        "state_maxdiff": tree_maxdiff(st1, st2),
        "metrics_maxdiff": float(max(
            max(abs(p - q) for p, q in zip(v, h2.metrics[k]))
            for k, v in h1.metrics.items())),
    }

    # ---------------- zero-byte accounting for absent clients ---------------
    def p4_net(mesh):
        net = P2PNetwork(M)
        strat = mk_p4(resident)()
        eng_cls = (lambda **kw: ShardedEngine(strat, mesh=mesh, **kw)) \
            if mesh is not None else (lambda **kw: Engine(strat, **kw))
        eng = eng_cls(eval_every=3, network=net,
                      schedule=ClientSampling(q=0.5))
        eng.fit(data8, rounds=8, key=key, batch_size=8)
        return net

    net1, net2 = p4_net(None), p4_net(mesh8)
    sched = ClientSampling(q=0.5)
    _, phase_key = jax.random.split(jax.random.fold_in(key, 0x9e37))
    masks = {r: np.asarray(sched.draw_mask(
        jax.random.fold_in(jax.random.fold_in(phase_key, r), 3), M))
        for r in range(8)}
    results["zero_byte_accounting"] = {
        "messages_equal": net1.num_messages() == net2.num_messages(),
        "bytes_equal": net1.total_bytes() == net2.total_bytes(),
        "nonzero": net2.num_messages() > 0,
        "endpoints_in_cohort": all(
            masks[m.rnd][m.src] == 1.0 and masks[m.rnd][m.dst] == 1.0
            for m in net2.log),
    }

    print(json.dumps(results))


if __name__ == "__main__":
    sys.exit(main())
