"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), in seconds (v5e constants):

  compute    = HLO_FLOPs_per_chip / peak_FLOP/s        (197 TFLOP/s bf16)
  memory     = HLO_bytes_per_chip / HBM_bw             (819 GB/s)
  collective = collective_bytes_per_chip / link_bw     (~50 GB/s/link ICI)

``cost_analysis()`` describes the per-device SPMD executable, so its flops /
bytes are already per-chip. Collective bytes are NOT in cost_analysis —
``collective_bytes`` parses the post-optimization HLO and sums the *result*
sizes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute (a send-volume proxy; each collective's output is what a
chip materializes over the interconnect).
"""
from __future__ import annotations

import re
from typing import Dict

PEAK_FLOPS = 197e12          # v5e bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# `%x = f32[8,128]{1,0} all-reduce(...)` or tuple results
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[^\]]*\]\S*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", )
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_SKIP_OPS = {"tuple", "get-tuple-element", "parameter", "bitcast", "constant",
             "after-all", "partition-id", "iota"}


def entry_region(hlo_text: str) -> str:
    """The ENTRY computation's body (top-level, post-fusion instructions)."""
    m = re.search(r"^ENTRY\b[^{]*\{", hlo_text, re.M)
    if not m:
        return hlo_text
    start = m.end()
    depth = 1
    i = start
    while i < len(hlo_text) and depth:
        c = hlo_text[i]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
        i += 1
    return hlo_text[start:i]


_ENTRY_OP_RE = re.compile(r"=\s*((?:\([^)]*\))|(?:\w+\[[^\]]*\]\S*))\s+([\w-]+)")


_COMP_SPLIT_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)[^\n{]*\{", re.M)


def hbm_bytes(hlo_text: str) -> Dict[str, float]:
    """Fused-HBM-traffic proxy: Σ over instructions at computation level of
    2 × output bytes (write + one read), skipping fusion-INTERNAL
    computations (their traffic stays in VMEM/registers on a fused backend)
    and boundary-free ops. While/conditional bodies count once — the dry-run
    extrapolates trip counts via two unroll factors.

    ``bytes accessed`` from cost_analysis() counts every unfused internal and
    overestimates HBM traffic ~10×; both are recorded (§Roofline)."""
    total = 0.0
    params = 0.0
    by_kind: Dict[str, float] = {}
    # split text into computation blocks; skip fusion bodies
    blocks = list(_COMP_SPLIT_RE.finditer(hlo_text))
    for i, m in enumerate(blocks):
        name = m.group(1)
        end = blocks[i + 1].start() if i + 1 < len(blocks) else len(hlo_text)
        body = hlo_text[m.end():end]
        # skip fusion internals + scalar reduce/compare wrapper computations;
        # KEEP region_* (while/cond bodies — trip counts are extrapolated)
        if ("fused_computation" in name or name.startswith("wrapped_")
                or name == "HloModule"):
            continue
        is_entry = hlo_text[max(0, m.start() - 6):m.start() + 5].strip().startswith("ENTRY") \
            or hlo_text[m.start():m.start() + 5] == "ENTRY"
        for om in _ENTRY_OP_RE.finditer(body):
            type_str, kind = om.group(1), om.group(2)
            sz = _shape_bytes(type_str)
            if kind == "parameter":
                if is_entry:
                    params += sz
                continue
            if kind in _SKIP_OPS or kind in ("while", "conditional", "call"):
                continue
            total += 2.0 * sz
            by_kind[kind] = by_kind.get(kind, 0.0) + sz
    top = dict(sorted(by_kind.items(), key=lambda kv: -kv[1])[:8])
    return {"total": total + params, "parameter_bytes": params, "top_ops": top}


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-op-kind result bytes of every collective in the compiled HLO.
    ``-start`` ops are counted; their ``-done`` twins are skipped (the result
    of -done duplicates the async buffer)."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        type_str, kind = m.group(1), m.group(2)
        span = hlo_text[m.start():m.end()]
        if f"{kind}-done(" in span:
            continue
        out[kind] += _shape_bytes(type_str)
        counts[kind] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = counts
    return out


_RG_LIST_RE = re.compile(r"replica_groups=\{(\{[\d,{} ]*\})\}")
# XLA iota (v2) format: replica_groups=[G,S]<=[d0,d1,...]T(p0,p1,...)
_RG_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")


def _iota_groups(g: int, s: int, dims, perm):
    import numpy as np
    n = 1
    for d in dims:
        n *= d
    ids = np.arange(n).reshape(dims)
    if perm:
        ids = ids.transpose(perm)
    return ids.reshape(g, s)


def pod_traffic(hlo_text: str, pod_size: int = 256) -> Dict[str, float]:
    """Split collective result bytes into intra-pod (ICI) vs cross-pod (DCN)
    by inspecting each collective's replica_groups (both explicit-list and
    iota formats). §Perf uses this to show the P4 step's group-internal
    topology keeps gradient traffic off the cross-pod links that consensus
    training exercises every step."""
    import numpy as np
    intra = cross = 0.0
    for m in _OP_RE.finditer(hlo_text):
        type_str, kind = m.group(1), m.group(2)
        line_end = hlo_text.find("\n", m.end())
        line = hlo_text[m.start():line_end if line_end > 0 else len(hlo_text)]
        sz = _shape_bytes(type_str)
        is_cross = None
        it = _RG_IOTA_RE.search(line)
        if it:
            g, s = int(it.group(1)), int(it.group(2))
            dims = [int(x) for x in it.group(3).split(",")]
            perm = [int(x) for x in it.group(4).split(",")] if it.group(4) else None
            groups = _iota_groups(g, s, dims, perm)
            is_cross = bool((np.ptp(groups // pod_size, axis=1) > 0).any())
        else:
            rg = _RG_LIST_RE.search(line)
            if rg:
                is_cross = False
                for grp in re.findall(r"\{([\d, ]+)\}", rg.group(1)):
                    ids = [int(t) for t in grp.replace(" ", "").split(",") if t]
                    if len({i // pod_size for i in ids}) > 1:
                        is_cross = True
                        break
        if is_cross is None:
            is_cross = True   # no groups listed => all participants
        if is_cross:
            cross += sz
        else:
            intra += sz
    return {"intra_pod_bytes": intra, "cross_pod_bytes": cross}


def roofline_terms(flops_per_chip: float, bytes_per_chip: float,
                   coll_bytes_per_chip: float) -> Dict[str, float]:
    compute = flops_per_chip / PEAK_FLOPS
    memory = bytes_per_chip / HBM_BW
    collective = coll_bytes_per_chip / ICI_BW
    terms = {"compute_s": compute, "memory_s": memory, "collective_s": collective}
    terms["bottleneck"] = max(terms, key=lambda k: terms[k] if k.endswith("_s") else -1)
    return terms


def model_flops(num_params: int, active_params: int, tokens: int,
                kind: str = "train") -> float:
    """6·N·D (dense) / 6·N_active·D (MoE); decode counts 2·N·D (fwd only)."""
    n = active_params or num_params
    per_tok = 6.0 * n if kind == "train" else 2.0 * n
    return per_tok * tokens
