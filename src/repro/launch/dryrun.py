"""Multi-pod dry-run: prove every (arch × input-shape × mesh) combination
lowers and compiles onto the production mesh, and extract roofline terms.

MUST be the very first thing in the process: 512 placeholder host devices
(jax locks device count on first init)."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import (DPConfig, InputShape, INPUT_SHAPES, MeshConfig,
                          P4Config, TrainConfig, replace)
from repro.configs import ARCHITECTURES, get_config
from repro.launch import roofline
from repro.launch.mesh import make_production_mesh
from repro.models import transformer
from repro.models.api import (build_model, cache_shardings, cache_specs,
                              input_shardings, input_specs, make_serve_step,
                              make_train_step, param_shardings)
from repro.models.module import abstract_params, partition_specs
from repro.sharding.rules import activation_sharding, make_rules

# archs whose long_500k run uses the framework's sliding-window variant
# (sub-quadratic requirement; SSM/hybrid run natively) — DESIGN.md §4.
_SWA_WINDOW = 8192


def _prep_config(arch: str, shape: InputShape, overrides: Dict[str, Any]):
    cfg = get_config(arch)
    notes = []
    if shape.name == "long_500k" and cfg.family not in ("ssm", "hybrid"):
        if cfg.window == 0:
            cfg = replace(cfg, window=_SWA_WINDOW)
            notes.append(f"long_500k uses sliding-window variant (window={_SWA_WINDOW})")
    from repro.config import _set_path
    for k, v in overrides.items():
        cfg = _set_path(cfg, k.split("."), v)
    return cfg, notes


def _active_params(cfg, specs) -> (int, int):
    """(total, active) parameter counts from the spec tree."""
    import jax.tree_util as jtu
    from repro.models.module import ParamSpec, is_spec
    total = expert = 0
    for _, s in jtu.tree_flatten_with_path(specs, is_leaf=is_spec)[0]:
        n = int(np.prod(s.shape))
        total += n
        if "experts" in s.dims:
            expert += n
    if cfg.moe.num_experts:
        k, E = cfg.moe.experts_per_token, cfg.moe.num_experts
        active = total - expert + int(expert * k / E)
    else:
        active = total
    return total, active


def _opt_state_shardings(param_pspecs, mesh):
    ns = lambda p: NamedSharding(mesh, p)
    mv = jax.tree_util.tree_map(ns, param_pspecs,
                                is_leaf=lambda x: isinstance(x, P))
    return {"m": mv, "v": mv, "count": ns(P())}


def _lower_for(cfg, shape: InputShape, mesh, mesh_cfg, rules, *, p4: bool,
               fsdp: bool):
    """Lower (not yet compiled) the step for this config onto the mesh."""
    api = build_model(cfg)
    params_abs = api.abstract()
    pspecs = partition_specs(api.specs, rules)
    ns = lambda p: NamedSharding(mesh, p)
    p_shard = jax.tree_util.tree_map(ns, pspecs, is_leaf=lambda x: isinstance(x, P))
    batch_abs = input_specs(cfg, shape)
    b_specs = input_shardings(cfg, shape, mesh_cfg, rules)
    b_shard = jax.tree_util.tree_map(ns, b_specs, is_leaf=lambda x: isinstance(x, P))

    with mesh, activation_sharding(mesh, rules):
        if p4:
            return _lower_p4(api, cfg, mesh, mesh_cfg, shape, pspecs, b_specs)
        if shape.kind == "train":
            train_cfg = TrainConfig()
            train_step, opt = make_train_step(api, train_cfg)
            opt_abs = jax.eval_shape(opt.init, params_abs)
            o_shard = _opt_state_shardings(pspecs, mesh)
            return jax.jit(
                train_step,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None),
            ).lower(params_abs, opt_abs, batch_abs)
        if shape.kind == "prefill":
            return jax.jit(
                api.prefill_fn, in_shardings=(p_shard, b_shard), out_shardings=None,
            ).lower(params_abs, batch_abs)
        # decode
        serve_step = make_serve_step(api)
        caches_abs = cache_specs(cfg, shape)
        c_specs = cache_shardings(cfg, shape, mesh_cfg, rules)
        c_shard = jax.tree_util.tree_map(ns, c_specs,
                                         is_leaf=lambda x: isinstance(x, P))
        return jax.jit(
            serve_step,
            in_shardings=(p_shard, c_shard, b_shard),
            out_shardings=(None, None, c_shard),
        ).lower(params_abs, caches_abs, batch_abs)


def _attention_correction(cfg, shape: InputShape) -> tuple:
    """Analytic (flops, bytes) of the chunked/flash attention inner loops,
    which the cost lowering counts only once (they remain scans).

    Causal(+window) pair count, matmul (QKᵀ + PV) + ~6 flop/score softmax;
    bytes = flash HBM streaming (q once, k/v once per q-block, o once).
    Training multiplies by 4 (fwd + remat re-fwd + 2×fwd for bwd).
    Only applies when the chunked path is active (s > 2048, not decode)."""
    s = shape.seq_len
    if shape.kind == "decode" or s <= 2048 or cfg.family == "ssm":
        return 0.0, 0.0
    b = shape.global_batch
    from repro.models.attention import n_q_heads
    hq, hkv, hd = n_q_heads(cfg), cfg.num_kv_heads, cfg.resolved_head_dim
    if cfg.family == "hybrid":
        from repro.models.transformer import hybrid_layout
        n_attn = hybrid_layout(cfg)[0]
    else:
        n_attn = cfg.num_layers
    w = cfg.window or s
    # visible (q, k) pairs: train uses the differentiable full-block sweep
    # (mask-only causality); prefill skips masked chunks dynamically.
    if shape.kind == "train":
        pairs = s * s                          # full masked sweep (see above)
    elif w < s:
        pairs = s * min(w, s) - (min(w, s) * (min(w, s) - 1)) // 2
    else:
        pairs = s * (s + 1) // 2
    matmul = 2 * 2 * b * pairs * hq * hd            # QKt + PV, 2 flops/MAC
    softmax = 6 * b * pairs * hq
    mult = 4.0 if shape.kind == "train" else 1.0
    flops = mult * n_attn * (matmul + softmax)
    nq = max(1, s // 512)                            # q-chunk count (block 512)
    itemsize = 2                                     # bf16 activations
    stream = itemsize * (2 * b * s * hq * hd + 2 * nq * b * min(w, s) * hkv * hd)
    bytes_ = mult * n_attn * stream
    return flops, bytes_


def _outer_count(cfg) -> int:
    """Trip count of the outer layer-stack scan (extrapolation target)."""
    from repro.models.transformer import hybrid_layout, xlstm_layout
    if cfg.family == "hybrid":
        return hybrid_layout(cfg)[0]
    if cfg.family == "ssm":
        return xlstm_layout(cfg)[0]
    return cfg.num_layers


def _measure(cfg, shape, mesh, mesh_cfg, rules, *, p4, fsdp):
    compiled = _lower_for(cfg, shape, mesh, mesh_cfg, rules,
                          p4=p4, fsdp=fsdp).compile()
    c = compiled.cost_analysis()
    hlo = compiled.as_text()
    hb = roofline.hbm_bytes(hlo)
    return {"flops": float(c.get("flops", 0.0)),
            "bytes_unfused": float(c.get("bytes accessed", 0.0)),
            "hbm": hb["total"], "hbm_top": hb["top_ops"],
            "coll": roofline.collective_bytes(hlo)}


def _extrap(v1, vu, u: int, L: int):
    """total = outside + L·body given f(1) and f(u) measurements."""
    if isinstance(v1, dict):
        return {k: _extrap(v1.get(k, 0), vu.get(k, 0), u, L)
                for k in set(v1) | set(vu)}
    if not isinstance(v1, (int, float)):
        return vu
    body = (vu - v1) / (u - 1)
    return max(v1 + (L - 1) * body, 0.0)


def _inner_scan_correction(cfg, shape: InputShape) -> tuple:
    """Analytic (flops, bytes) for the once-counted chunked-recurrence scans
    (Mamba2 SSD inter-chunk state scan; mLSTM chunkwise scan). Their bodies
    are exact, small formulas; unrolling them at 32k–500k sequence lengths
    explodes HLO size, so we count them on paper instead.

    The measured HLO already contains ONE body per layer (the scan's single
    counted iteration), so corrections add (nc − 1) bodies per layer."""
    s = shape.seq_len if shape.kind != "decode" else 1
    if s <= 1:
        return 0.0, 0.0
    b = shape.global_batch
    mult = 4.0 if shape.kind == "train" else 1.0
    flops = bytes_ = 0.0
    if cfg.family == "hybrid" and cfg.ssm.state_dim:
        H, N = cfg.ssm.num_heads, cfg.ssm.state_dim
        P = cfg.ssm.head_dim or (cfg.ssm.expand * cfg.d_model) // H
        c = cfg.ssm.chunk_size
        nc = max(1, s // c)
        # body: y_off einsum (2cHNP) + state decay/update (3HNP)
        body_f = b * H * (2 * c * N * P + 3 * N * P)
        body_b = 4 * b * H * (2 * c * N + c * P + 2 * N * P)   # fp32 operands
        flops += mult * cfg.num_layers * (nc - 1) * body_f
        bytes_ += mult * cfg.num_layers * (nc - 1) * body_b
    if cfg.family == "ssm" and cfg.xlstm_pattern:
        from repro.models.transformer import xlstm_layout
        units, pat = xlstm_layout(cfg)
        n_mlstm = units * sum(1 for k in pat if k == "m")
        H = cfg.num_heads
        hd = cfg.d_model // H
        c = min(256, s)
        nc = max(1, s // c)
        # chunk body: qkᵀ + h_intra + n_vec (3·2·c²·hd) + inter/carry (3·2·c·hd²)
        body_f = b * H * (6 * c * c * hd + 6 * c * hd * hd + 12 * c * c)
        body_b = 4 * b * H * (3 * c * hd + 2 * hd * hd + 4 * c * c)
        flops += mult * n_mlstm * (nc - 1) * body_f
        bytes_ += mult * n_mlstm * (nc - 1) * body_b
    return flops, bytes_


def _slstm_correction(cfg, shape: InputShape) -> float:
    """Analytic flops for the sLSTM time recurrence (its seq scan cannot be
    unrolled at 4k–32k; body ≈ 4 block-diagonal recurrent matmuls)."""
    if cfg.family != "ssm" or "s" not in (cfg.xlstm_pattern or ()):
        return 0.0
    s = 1 if shape.kind == "decode" else shape.seq_len
    if s <= 1:
        return 0.0
    from repro.models.transformer import xlstm_layout
    units, pat = xlstm_layout(cfg)
    n_slstm = units * sum(1 for k in pat if k == "s")
    H = cfg.num_heads
    hd = cfg.d_model // H
    step = 2 * 4 * H * hd * hd + 30 * H * hd        # recurrence + pointwise
    mult = 3.0 if shape.kind == "train" else 1.0     # fwd+bwd≈3x fwd
    return mult * n_slstm * shape.global_batch * (s - 1) * step


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool = False,
                p4: bool = False, overrides: Optional[Dict[str, Any]] = None,
                fsdp: bool = True, verbose: bool = True,
                cost_variant: bool = True,
                rule_overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    shape = INPUT_SHAPES[shape_name]
    cfg, notes = _prep_config(arch, shape, overrides or {})
    mesh_cfg = MeshConfig(multi_pod=multi_pod)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(cfg, mesh_cfg, kind=shape.kind, fsdp=fsdp)
    if rule_overrides:
        rules.update(rule_overrides)
        notes.append(f"rule_overrides={rule_overrides}")
    api = build_model(cfg)

    t0 = time.time()
    lowered = _lower_for(cfg, shape, mesh, mesh_cfg, rules, p4=p4, fsdp=fsdp)
    t_lower = time.time() - t0
    if verbose:
        print(f"[dryrun] lowered in {t_lower:.1f}s; compiling ...", flush=True)
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    if verbose:
        print(f"[dryrun] compiled in {t_compile:.1f}s", flush=True)

    mem = compiled.memory_analysis()

    # ---- cost-faithful pass: XLA cost_analysis counts while bodies ONCE, so
    # we lower twice (layer-scan unroll factors 1 and u) and extrapolate
    # total = f1 + (L-1)·(fu - f1)/(u-1). The chunked-attention inner (q, kv)
    # scans stay loops in both; their cost is exactly computable and added
    # analytically (_attention_correction), as is the sLSTM time recurrence.
    outer = _outer_count(cfg)
    cost_src = f"unroll-extrapolated(L={outer})+analytic-attn"
    try:
        if not cost_variant:
            raise RuntimeError("cost variant disabled")
        u = 2 if outer % 2 == 0 else 3
        u = min(u, outer)
        m1 = _measure(replace(cfg, unroll_layers=1, unroll_inner=True),
                      shape, mesh, mesh_cfg, rules, p4=p4, fsdp=fsdp)
        if u > 1:
            mu = _measure(replace(cfg, unroll_layers=u, unroll_inner=True),
                          shape, mesh, mesh_cfg, rules, p4=p4, fsdp=fsdp)
            meas = {k: _extrap(m1[k], mu[k], u, outer) for k in m1}
        else:
            meas = m1
    except Exception as e:  # fall back to the scan artifact, flagged
        cost_src = f"scan-fallback ({type(e).__name__}: {e})"
        c = compiled.cost_analysis()
        hlo0 = compiled.as_text()
        meas = {"flops": float(c.get("flops", 0.0)),
                "bytes_unfused": float(c.get("bytes accessed", 0.0)),
                "hbm": roofline.hbm_bytes(hlo0)["total"],
                "hbm_top": roofline.hbm_bytes(hlo0)["top_ops"],
                "coll": roofline.collective_bytes(hlo0)}

    chips = mesh_cfg.num_devices
    attn_fl, attn_by = _attention_correction(cfg, shape)
    inner_fl, inner_by = _inner_scan_correction(cfg, shape)
    flops = meas["flops"] + (_slstm_correction(cfg, shape) + attn_fl + inner_fl) / chips
    byts_raw = meas["bytes_unfused"]
    byts = meas["hbm"] + (attn_by + inner_by) / chips
    coll = meas["coll"]
    hbm = {"top_ops": meas.get("hbm_top", {})}
    terms = roofline.roofline_terms(flops, byts, coll["total"])
    total_p, active_p = _active_params(cfg, api.specs)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mf = roofline.model_flops(total_p, active_p, tokens,
                              "train" if shape.kind == "train" else "decode")
    mf_per_chip = mf / chips
    pods = roofline.pod_traffic(compiled.as_text()) if multi_pod else None
    result = {
        "arch": arch, "shape": shape_name, "mesh": "2x16x16" if multi_pod else "16x16",
        "p4": p4, "notes": notes, "pod_traffic": pods,
        "overrides": overrides or {},
        "params_total": total_p, "params_active": active_p,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops_per_chip": flops, "bytes_per_chip": byts,
        "bytes_unfused_per_chip": byts_raw,
        "hbm_top_ops": hbm["top_ops"],
        "collective_bytes_per_chip": coll["total"],
        "collectives": {k: v for k, v in coll.items() if k not in ("total",)},
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0) or 0)
                          + (getattr(mem, "argument_size_in_bytes", 0) or 0),
        },
        "roofline": terms,
        "cost_source": cost_src,
        "model_flops_per_chip": mf_per_chip,
        "useful_flops_ratio": (mf_per_chip / flops) if flops else None,
    }
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {result['mesh']}"
              f"{' × P4' if p4 else ''}: lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"  memory_analysis: args={result['memory']['argument_bytes']}"
              f" temp={result['memory']['temp_bytes']} out={result['memory']['output_bytes']}")
        print(f"  cost_analysis: flops/chip={flops:.3e} bytes/chip={byts:.3e}"
              f" collective_bytes/chip={coll['total']:.3e}")
        print(f"  roofline: compute={terms['compute_s']:.4f}s memory={terms['memory_s']:.4f}s"
              f" collective={terms['collective_s']:.4f}s -> {terms['bottleneck']}")
    return result


def _lower_p4(api, cfg, mesh, mesh_cfg, shape, pspecs, b_specs):
    """P4 dual-model step over G groups == pod axis (multi-pod only)."""
    from repro.core.p4 import make_p4_lm_step
    from repro.optim import make_optimizer
    assert mesh_cfg.multi_pod, "P4 dry-run uses the pod axis as the group axis"
    G = mesh_cfg.pods
    train_cfg = TrainConfig()
    dp_cfg = DPConfig(microbatches=4)
    p4_cfg = P4Config()
    step = make_p4_lm_step(api, api, train_cfg, dp_cfg, p4_cfg)
    opt = make_optimizer(train_cfg)

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct((G,) + tuple(l.shape), l.dtype), tree)

    params_abs = stack(api.abstract())
    params_abs = {"private": params_abs, "proxy": params_abs}
    opt_abs = jax.eval_shape(jax.vmap(opt.init), params_abs["private"])
    opt_abs = {"private": opt_abs, "proxy": opt_abs}

    ns = lambda p: NamedSharding(mesh, p)
    def stack_spec(p):
        return ns(P(*(("pod",) + tuple(p))))
    pp = jax.tree_util.tree_map(stack_spec, pspecs, is_leaf=lambda x: isinstance(x, P))
    p_shard = {"private": pp, "proxy": pp}
    mv = pp
    o_shard = {"private": {"m": mv, "v": mv, "count": ns(P(None))},
               "proxy": {"m": mv, "v": mv, "count": ns(P(None))}}
    b, s = shape.global_batch, shape.seq_len
    batch_abs = {"tokens": jax.ShapeDtypeStruct((G, b // G, s), jnp.int32)}
    b_shard = {"tokens": ns(P("pod", "data", None))}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.jit(
        step,
        in_shardings=(p_shard, o_shard, b_shard, ns(P())),
        out_shardings=(p_shard, o_shard, None),
    ).lower(params_abs, opt_abs, batch_abs, key)


# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", choices=list(ARCHITECTURES), required=False)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES), required=False)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--p4", action="store_true", help="lower the P4 dual-model step")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--set", nargs="*", default=[], help="ModelConfig overrides k=v")
    ap.add_argument("--out", default=None, help="append JSON result to this file")
    ap.add_argument("--no-cost", action="store_true",
                    help="skip the unrolled cost-variant lowering")
    ap.add_argument("--rule", nargs="*", default=[],
                    help="sharding-rule overrides, e.g. vocab=none heads=model")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    rule_overrides = {}
    for kv in args.rule:
        k, v = kv.split("=", 1)
        rule_overrides[k] = None if v.lower() == "none" else v

    result = lower_combo(args.arch, args.shape, multi_pod=args.multi_pod,
                         p4=args.p4, overrides=overrides, fsdp=not args.no_fsdp,
                         cost_variant=not args.no_cost,
                         rule_overrides=rule_overrides or None)
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(result) + "\n")


if __name__ == "__main__":
    main()
