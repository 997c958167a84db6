#!/usr/bin/env python3
"""Chip smoke test: the P4 paper path end to end on one TPU chip.

    python chip_smoke.py               # one chip: P4 fit + DP local baseline
    python chip_smoke.py --chips 4     # client-mesh P4 fit vs the same fit
                                       # on one chip (needs four chips)
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]
                                       # CPU rehearsal at a tiny size, Pallas
                                       # kernels in interpret mode

The model is the paper's linear classifier at full width
(``repro.configs.paper_linear.config("cifar10")``: 15552 ScatterNet features
x 10 classes, 155,530 parameters per model, a private and a proxy copy per
client). M = 64 clients hold R = 240 synthetic CIFAR-10 images each
(192 train / 48 test, two classes per client); the features are computed on
the chip. ``P4Trainer.fit`` runs its bootstrap, Phase-1 grouping and Phase-2
DP co-training at eps = 15; the DP local-only baseline then trains on the
same data, which is the path through the fused ``dp_round`` kernel.

Checks (each printed as ``CHECK <name>: PASS|FAIL``):
  * every main-path kernel (dp_clip, l1_distance, dp_round) resolved to
    compiled Pallas on this run (the ``kernels.backend`` probe);
  * Phase-1 distances, Pallas vs the jnp reference: max relative error
    <= L1_RTOL, and the groups formed from either are identical;
  * one ``dp_gradients`` call on one client's batch, Pallas vs reference:
    max absolute error <= GRAD_ATOL, and on both backends the noised
    gradient is the clean one plus the canonical Eq. 11 draw, bit for bit;
  * one fused ``dp_round`` call, Pallas vs the closed-form reference:
    max absolute error <= GRAD_ATOL;
  * final mean personalized accuracy finite and above 1/C.
References run under ``jax.default_matmul_precision("highest")`` (XLA on
TPU runs f32 matmuls in bf16 passes by default); the kernel side runs under
the same context, which reaches only the XLA ops around the kernels.

With ``--chips 4`` it runs only ``P4Trainer.fit(..., mesh=make_client_mesh(4))``
and the same call without a mesh, on the same data and seed, and checks that
the groups are identical and the accuracy histories bit-equal (as the CPU
equivalence tier asserts for P4 end to end); it prints the final states'
max difference and each device's memory.

Informative lines: compile seconds from the ``jax.compile`` probe (backend
compiles or persistent-cache loads), the DP routes traced (the ``dp.path``
probe), steady co-train rounds/s timed around
``block_until_ready``, ``peak_bytes_in_use`` and the tuned tiles. The compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<repo>/.jax_cache``.

The last line of stdout is ``{"ok": true, "device": {...}}`` only when every
check passed on a TPU; otherwise the script exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
# before anything compiles: the cache directory is read once, at the first
# compile, and a fixed path is what lets a later run find the entries
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(REPO, ".jax_cache"))
sys.path.insert(0, os.path.join(REPO, "src"))

from repro.baselines import local  # noqa: E402
from repro.baselines.common import ce_loss  # noqa: E402
from repro.config import DPConfig, KernelConfig, RunConfig, replace  # noqa: E402
from repro.configs import paper_linear  # noqa: E402
from repro.core import dp as dp_lib  # noqa: E402
from repro.core.grouping import (flatten_clients,  # noqa: E402
                                 greedy_group_formation)
from repro.core.p4 import P4Strategy, P4Trainer  # noqa: E402
from repro.core.scattering import scatternet_features  # noqa: E402
from repro.data import make_image_task_pool, shard_partition  # noqa: E402
from repro.data.pipeline import stack_client_data, train_test_split  # noqa: E402
from repro.engine import Engine, FederatedData, make_schedule  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.kernels.dp_clip.ref import add_flat_noise  # noqa: E402
from repro.kernels.dp_round.ref import dp_round_closed  # noqa: E402
from repro.kernels.l1_distance import ref as l1_ref  # noqa: E402
from repro.launch.mesh import make_client_mesh  # noqa: E402
from repro.obs import COMPILE_STATS as COMPILES, probe_deltas  # noqa: E402
from repro.utils.pytree import tree_flatten_concat  # noqa: E402

L1_RTOL = 1e-4      # Phase-1 distance, max |pallas - ref| / ref off-diagonal
GRAD_ATOL = 1e-5    # DP gradient entries, max |pallas - ref|
KERNELS = ("dp_clip", "l1_distance", "dp_round")


def _fail(msg: str, code: int = 1):
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def _log(msg: str) -> None:
    print(msg, flush=True)


@dataclass
class Setup:
    """One smoke run: configuration, the clients' data and the checks."""
    cfg: RunConfig
    backend: str
    F: int
    C: int
    M: int
    n_tr: int
    rounds: int
    boot: int
    eval_every: int
    seed: int
    data: FederatedData
    checks: list = field(default_factory=list)

    def check(self, name: str, passed, detail: str) -> None:
        self.checks.append(bool(passed))
        _log(f"CHECK {name}: {'PASS' if passed else 'FAIL'} {detail}")

    def key(self):
        return jax.random.PRNGKey(self.seed)

    def fit(self, trainer, mesh=None, capture=None):
        """``P4Trainer.fit`` through its public entry point. ``capture`` (a
        dict) receives the flattened bootstrap proxy weights that Phase 1
        groups on and client 0's proxy model — copies, since co-training
        then donates the bootstrap state's buffers."""
        if capture is not None:
            form = trainer.form_groups

            def form_groups(states, seed=0, topology=None):
                capture["weights"] = flatten_clients(states["proxy"])
                capture["client0"] = jax.tree_util.tree_map(
                    lambda t: t[0], states["proxy"])
                return form(states, seed, topology=topology)
            trainer.form_groups = form_groups
        d = self.data
        return trainer.fit(d.train_x, d.train_y, d.test_x, d.test_y,
                           rounds=self.rounds, eval_every=self.eval_every,
                           bootstrap_rounds=self.boot, seed=self.seed,
                           key=self.key(), mesh=mesh)


def _per_example_chunk(M: int, B: int, D: int, budget: int) -> int:
    """0 (one vmap over the batch) when the (M, B, D) f32 per-example stack
    and its flattened copy fit in half the budget; else the largest chunk c
    dividing B whose two (M, c, D) stacks fit in a quarter of it."""
    if 2 * M * B * D * 4 <= budget // 2:
        return 0
    fits = [c for c in range(1, B + 1)
            if B % c == 0 and 2 * M * c * D * 4 <= budget // 4]
    if not fits:
        raise RuntimeError(f"no per-example chunk fits {budget} bytes")
    return max(fits)


def _client_data(M: int, R: int, C: int, F: int, seed: int):
    """Synthetic CIFAR-10 pool, two classes per client, ScatterNet features
    computed on the default device. Returns FederatedData and n_train."""
    n_tr = R - max(1, int(R * 0.2))
    shards = math.ceil(M * 2 / C)        # shard_partition's shards per class
    imgs, labels, _ = make_image_task_pool(
        "cifar10", seed=seed, samples_per_class=(shards + 1) * (R // 2))
    clients = shard_partition(labels, M, classes_per_client=2,
                              samples_per_client=R, seed=seed)
    tr, te = zip(*[train_test_split(c, 0.2, seed) for c in clients])
    pool_idx = np.arange(len(labels))
    tr_idx, try_ = stack_client_data(pool_idx, labels, list(tr), n_tr)
    te_idx, tey = stack_client_data(pool_idx, labels, list(te), R - n_tr)
    feat_fn = jax.jit(scatternet_features)
    batch = 200 if len(imgs) % 200 == 0 else len(imgs)
    feats = jnp.concatenate([feat_fn(jnp.asarray(imgs[i:i + batch]))
                             for i in range(0, len(imgs), batch)])
    if feats.shape[1] != F:
        raise RuntimeError(f"feature width {feats.shape[1]} != {F}")
    data = FederatedData(feats[jnp.asarray(tr_idx)], jnp.asarray(try_),
                         feats[jnp.asarray(te_idx)], jnp.asarray(tey))
    jax.block_until_ready((data.train_x, data.test_x))
    return data, n_tr, len(imgs)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny M/R/rounds, interpret kernels, "
                         "no ok line")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        _fail(f"no TPU: JAX reports platform {dev.platform!r}", 2)
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} devices, "
              f"JAX reports {len(devices)}", 2)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    _log(f"device: {json.dumps(device)}")

    if args.rehearse:
        M, R, rounds, boot, eval_every = 16, 40, 6, 2, 3
        backend, budget = "interpret", 1 << 30
    else:
        M, R, rounds, boot, eval_every = 64, 240, 20, 4, 10
        backend = "pallas"
        budget = int(dev.memory_stats()["bytes_limit"])
    spec = paper_linear.config("cifar10")
    F, C = spec["feat_dim"], spec["num_classes"]
    D = F * C + C

    t0 = time.perf_counter()
    data, n_tr, pool = _client_data(M, R, C, F, args.seed)
    _log(f"data: M={M} clients x R={R} ({n_tr} train / {R - n_tr} test), "
         f"F={F} C={C} D={D}, pool {pool} images, "
         f"{time.perf_counter() - t0:.2f}s")

    chunk = _per_example_chunk(M, n_tr, D, budget)
    _log(f"memory: per-example stack {M * n_tr * D * 4 / 2**30:.2f} GiB vs "
         f"budget {budget / 2**30:.2f} GiB -> "
         + (f"DPConfig.per_example_chunk={chunk}" if chunk
            else "full per-example vmap"))
    run = spec["run"]
    cfg = replace(run, kernels=KernelConfig(backend=backend),
                  dp=replace(run.dp, rounds=rounds, per_example_chunk=chunk))
    s = Setup(cfg=cfg, backend=backend, F=F, C=C, M=M, n_tr=n_tr,
              rounds=rounds, boot=boot, eval_every=eval_every, seed=args.seed,
              data=data)

    (_four_chip if args.chips == 4 else _one_chip)(s)

    stats = dev.memory_stats() or {}
    _log(f"memory: peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
         f"bytes_limit={stats.get('bytes_limit')}")
    _log("compile: " + json.dumps(
        {k: COMPILES[k] for k in ("compile_s", "compiles", "cache_hits",
                                  "cache_misses")}))
    _log("dp path (traces per route): " + json.dumps(dp_lib.DP_PATH))
    if not all(s.checks):
        _fail(f"{s.checks.count(False)} check(s) failed")
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "device": device}))
        return
    print(json.dumps({"ok": True, "device": device}))


def _one_chip(s: Setup) -> None:
    kcfg, d = s.cfg.kernels, s.data

    # ---------------- P4: bootstrap -> grouping -> DP co-training ----------
    trainer = P4Trainer(feat_dim=s.F, num_classes=s.C, cfg=s.cfg)
    boot = {}
    c0, t0 = COMPILES.snapshot(), time.perf_counter()
    with probe_deltas("kernels.backend", "kernels.autotune") as probes:
        states, groups, hist = s.fit(trainer, capture=boot)
        jax.block_until_ready(states)
        p4_s = time.perf_counter() - t0
        p4_compile = COMPILES["compile_s"] - c0["compile_s"]

        # ---------------- DP local-only baseline (fused dp_round) ----------
        c1, t1 = COMPILES.snapshot(), time.perf_counter()
        _, lh = local.train(d.train_x, d.train_y, d.test_x, d.test_y,
                            rounds=s.rounds, lr=0.5, batch_size=s.n_tr,
                            eval_every=s.eval_every, seed=s.seed,
                            dp_cfg=DPConfig(clip_norm=1.0),
                            sigma=trainer.sigma, kernels=kcfg)
        local_s = time.perf_counter() - t1
        local_compile = COMPILES["compile_s"] - c1["compile_s"]
    used = {k: v for k, v in probes["kernels.backend"].items() if v}
    _log(f"p4: sigma={trainer.sigma:.6f} groups={len(groups)} "
         f"history={list(zip(hist.rounds, hist.accuracy))} "
         f"wall={p4_s:.2f}s compile={p4_compile:.2f}s")
    _log(f"local-dp: history={list(zip(lh.rounds, lh.accuracy))} "
         f"wall={local_s:.2f}s compile={local_compile:.2f}s")
    _log(f"kernels: backend={json.dumps(used)} "
         f"autotune={json.dumps(probes['kernels.autotune'])} "
         f"tiles={dispatch.tuned_tiles()}")
    for k in KERNELS:
        mine = {n: v for n, v in used.items() if n.startswith(k + ":")}
        s.check(f"{k}_resolved_{s.backend}",
                mine.get(f"{k}:{s.backend}", 0) > 0 and len(mine) == 1,
                f"counts={mine}")

    # ---------------- steady co-train rounds/s (no compile in window) ------
    strategy = P4Strategy(trainer=trainer)
    strategy.set_groups(groups, s.M)
    engine = Engine(strategy, eval_every=s.eval_every,
                    schedule=make_schedule(s.cfg.schedule))

    def cotrain():
        # the engine donates its state carry: hand it a copy, untimed
        fresh = jax.block_until_ready(jax.tree_util.tree_map(jnp.copy,
                                                             states))
        t = time.perf_counter()
        st, _ = engine.fit(d, rounds=s.rounds, start_round=s.boot,
                           state=fresh, key=s.key(), batch_size=s.n_tr,
                           evaluate=False)
        jax.block_until_ready(st)
        return time.perf_counter() - t

    cotrain()                                  # warm: compiles this chunk
    c2 = COMPILES.snapshot()
    with probe_deltas("engine.chunk_cache") as dc:
        times = [cotrain() for _ in range(3)]
    win = s.rounds - s.boot
    _log(f"steady: co-train {win} rounds x3 in {times} s -> "
         f"{win / min(times):.4f} rounds/s (best), "
         f"{win / sorted(times)[1]:.4f} rounds/s (median); compiles in "
         f"window={COMPILES['compiles'] - c2['compiles']} "
         f"chunk_cache={json.dumps(dc['engine.chunk_cache'])}")

    # ---------------- Pallas vs reference checks ---------------------------
    kref = KernelConfig(backend="ref")
    p4c = s.cfg.p4
    with jax.default_matmul_precision("highest"):
        d_k = np.asarray(dispatch.pairwise_l1(boot["weights"], kernels=kcfg))
        d_r = np.asarray(l1_ref.pairwise_l1(boot["weights"]))
    off = ~np.eye(s.M, dtype=bool)
    rel = float(np.max(np.abs(d_k - d_r)[off] / np.abs(d_r)[off]))
    s.check("l1_distance_vs_ref", rel <= L1_RTOL,
            f"max_rel_err={rel!r} tol={L1_RTOL}")
    g_k = greedy_group_formation(d_k, p4c.group_size, p4c.sample_peers,
                                 s.seed)
    g_r = greedy_group_formation(d_r, p4c.group_size, p4c.sample_peers,
                                 s.seed)
    s.check("groups_identical", g_k == g_r == groups,
            f"groups={len(groups)} sizes={sorted(len(g) for g in groups)}")

    params = boot["client0"]
    batch = {"x": d.train_x[0], "y": d.train_y[0]}
    loss = ce_loss(trainer.apply_fn)
    key = jax.random.fold_in(s.key(), 7)
    sigma, clip = trainer.sigma, s.cfg.dp.clip_norm

    def grads(kc, sig):
        return tree_flatten_concat(dp_lib.dp_gradients(
            loss, params, batch, key, clip=clip, sigma=sig,
            per_example_chunk=s.cfg.dp.per_example_chunk, kernels=kc))

    with jax.default_matmul_precision("highest"):
        out = {(n, sig): np.asarray(grads(kc, sig)) for n, kc in
               (("kernel", kcfg), ("ref", kref)) for sig in (0.0, sigma)}
        canon = {n: np.asarray(add_flat_noise(jnp.asarray(out[(n, 0.0)]),
                                              key, sigma, clip, float(s.n_tr)))
                 for n in ("kernel", "ref")}
    err = float(np.max(np.abs(out[("kernel", 0.0)] - out[("ref", 0.0)])))
    s.check("dp_gradients_vs_ref", err <= GRAD_ATOL,
            f"max_abs_err={err!r} tol={GRAD_ATOL} "
            f"max_abs_grad={float(np.max(np.abs(out[('ref', 0.0)])))!r}")
    s.check("dp_noise_bit_identical",
            all(np.array_equal(out[(n, sigma)], canon[n])
                for n in ("kernel", "ref")),
            f"sigma={sigma!r} (noised == clean + canonical draw, both "
            f"backends)")

    with jax.default_matmul_precision("highest"):
        r_k = dispatch.dp_round(loss, params, batch["x"], batch["y"], key,
                                clip=clip, sigma=sigma, kernels=kcfg)
        r_r = dp_round_closed(params, batch["x"], batch["y"], key, clip=clip,
                              sigma=sigma)
    err = float(max(np.max(np.abs(np.asarray(r_k[k]) - np.asarray(r_r[k])))
                    for k in r_r))
    s.check("dp_round_vs_closed_ref", err <= GRAD_ATOL,
            f"max_abs_err={err!r} tol={GRAD_ATOL}")

    acc, lacc = hist.accuracy[-1], lh.accuracy[-1]
    s.check("p4_accuracy", math.isfinite(acc) and acc > 1.0 / s.C,
            f"final mean personalized accuracy={acc!r} (1/C={1.0 / s.C}) "
            f"local-dp={lacc!r}")
    s.check("local_accuracy", math.isfinite(lacc), f"local-dp={lacc!r}")


def _four_chip(s: Setup) -> None:
    runs = {}
    for name, mesh in (("one_chip", None), ("mesh4", make_client_mesh(4))):
        trainer = P4Trainer(feat_dim=s.F, num_classes=s.C, cfg=s.cfg)
        c0, t0 = COMPILES.snapshot(), time.perf_counter()
        st, groups, hist = s.fit(trainer, mesh=mesh)
        jax.block_until_ready(st)
        wall = time.perf_counter() - t0
        runs[name] = (st, groups, hist)
        mem = [(dv.id, (dv.memory_stats() or {}).get("peak_bytes_in_use"))
               for dv in jax.devices()]
        _log(f"{name}: history={list(zip(hist.rounds, hist.accuracy))} "
             f"wall={wall:.2f}s "
             f"compile={COMPILES['compile_s'] - c0['compile_s']:.2f}s "
             f"peak_bytes_in_use per device={mem}")
    (s1, g1, h1), (s2, g2, h2) = runs["one_chip"], runs["mesh4"]
    s.check("groups_identical", g1 == g2, f"groups={len(g1)}")
    gap = max(abs(a - b) for a, b in zip(h1.accuracy, h2.accuracy))
    s.check("accuracy_histories_bit_equal",
            h1.rounds == h2.rounds and h1.accuracy == h2.accuracy,
            f"rounds={h1.rounds} max_gap={gap!r}")
    diff = max(float(np.max(np.abs(np.asarray(a, np.float64)
                                   - np.asarray(b, np.float64))))
               for a, b in zip(jax.tree_util.tree_leaves(s1),
                               jax.tree_util.tree_leaves(s2)))
    _log(f"state: max |one_chip - mesh4| = {diff!r}")


if __name__ == "__main__":
    main()
