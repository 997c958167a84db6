"""Logical-axis sharding rules.

One rules dict per (architecture, mesh, step-kind). Axes are only assigned
when the dimension size divides the mesh-axis size — GSPMD requires even
shards for jit in/out shardings, and per-arch head counts differ (e.g. the
40-head archs cannot shard heads over a 16-way model axis; they fall back to
replicated heads + sharded d_ff/vocab, see DESIGN.md §4).

Logical dim vocabulary (used by every ParamSpec in repro.models):

  batch        activation batch            -> ("pod","data") / ("data",)
  seq          activation sequence         -> None (context-parallel = hillclimb)
  kv_seq       KV-cache sequence           -> "model" when heads don't shard
  embed        d_model                     -> "data" (FSDP)
  heads        query heads                 -> "model" if divisible
  kv_heads     KV heads (GQA)              -> "model" if divisible
  head_dim                                  -> None
  ffn          MLP hidden                  -> "model"
  vocab        vocabulary                  -> "model"
  experts      MoE expert dim              -> "data" if divisible (EP), else None
  d_inner      SSM inner dim               -> "model"
  ssm_state / conv / codebooks / layers    -> None
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec

from repro.config import MeshConfig, ModelConfig

# ---------------------------------------------------------------------------
# Activation-sharding context: model code calls shard_act(x, dims) at block
# boundaries; under an active context this pins activations (e.g. batch ->
# "data"), which is what forces GSPMD to all-gather FSDP-sharded weights
# instead of replicating activations (ZeRO-3 semantics). Outside the context
# (unit tests, single-device runs) it is a no-op.
# ---------------------------------------------------------------------------

_CTX = threading.local()


@contextlib.contextmanager
def activation_sharding(mesh, rules: dict):
    prev = getattr(_CTX, "val", None)
    _CTX.val = (mesh, rules)
    try:
        yield
    finally:
        _CTX.val = prev


def context_axis_size(axis: str) -> int:
    """Size of a mesh axis under the active activation-sharding context
    (1 outside any context — single-device tests degrade gracefully)."""
    ctx = getattr(_CTX, "val", None)
    if ctx is None:
        return 1
    mesh, _ = ctx
    return int(mesh.shape.get(axis, 1))


def _manual_axes() -> set:
    """Mesh axes currently under manual (shard_map) control — constraints
    inside the region must not mention them."""
    try:
        am = jax.sharding.get_abstract_mesh()
        if am is None or not am.axis_names:
            return set()
        return {n for n, t in zip(am.axis_names, am.axis_types)
                if "Manual" in str(t)}
    except Exception:
        return set()


def shard_act(x, dims):
    ctx = getattr(_CTX, "val", None)
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = logical_spec(dims, rules)
    manual = _manual_axes()
    # drop manual axes and axes that don't divide the dim (GSPMD needs even shards)
    axes = []
    for size, ax in zip(x.shape, spec):
        if ax is not None:
            ax_t = tuple(a for a in ((ax,) if isinstance(ax, str) else ax)
                         if a not in manual)
            n = 1
            for a in ax_t:
                n *= mesh.shape[a]
            if not ax_t or size % n or size == 0:
                ax = None
            else:
                ax = ax_t[0] if len(ax_t) == 1 else ax_t
        axes.append(ax)
    if manual:
        # inside shard_map: raw PartitionSpec resolves on the ambient mesh
        return jax.lax.with_sharding_constraint(x, PartitionSpec(*axes))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, PartitionSpec(*axes)))


def _axis_if(divides: int, size: int, axis):
    return axis if size > 0 and divides > 0 and divides % size == 0 else None


# ---------------------------------------------------------------------------
# Client (federation) axis: the sharded engine runs the scanned round body
# under shard_map over a mesh axis holding disjoint client shards. The spec
# derivation lives here so the engine and the LM-scale pod path agree on how
# client-stacked pytrees map onto a mesh.
# ---------------------------------------------------------------------------

CLIENT_AXIS = "clients"


def client_specs(tree, stacked: int, axis: str = CLIENT_AXIS):
    """PartitionSpec tree for a client-stacked pytree: leaves whose leading
    dim equals ``stacked`` shard over ``axis``; everything else replicates.

    ``stacked`` is the (padded) client count — an exact-size match, not a
    divisibility heuristic, so a replicated (D,) leaf with D == stacked is
    the only ambiguity; strategies whose carry is server-style (no client
    axis at all, e.g. FedAvg's global model) override
    ``Strategy.state_client_stacked`` to force full replication instead of
    relying on this shape test."""
    def spec(leaf):
        if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == stacked:
            return PartitionSpec(axis)
        return PartitionSpec()
    return jax.tree_util.tree_map(
        spec, tree, is_leaf=lambda x: hasattr(x, "shape"))


def batch_axes(mesh_cfg: MeshConfig):
    """Mesh axes that shard the global batch (pod joins data in multi-pod)."""
    return ("pod", "data") if mesh_cfg.multi_pod else ("data",)


def make_rules(model: ModelConfig, mesh: MeshConfig, *, kind: str = "train",
               fsdp: bool = True) -> dict:
    dsz, msz = mesh.data, mesh.model
    hd = model.resolved_head_dim
    n_q = max(model.pad_attn_heads_to, model.num_heads)
    heads_ax = _axis_if(n_q, msz, "model")
    kv_heads_ax = _axis_if(model.num_kv_heads, msz, "model")
    # GQA: logits einsum needs q- and kv-heads co-sharded; if kv heads don't
    # divide, shard q heads only (kv replicated is cheap for small kv counts).
    rules = {
        "batch": batch_axes(mesh),
        "seq": None,
        # decode against a long cache: if heads can't shard, shard the cache
        # sequence dim over "model" so the (1-token q · full K) contraction is
        # distributed (flash-decoding style partial-softmax, handled by XLA).
        "kv_seq": ("model" if (kind == "decode" and kv_heads_ax is None) else None),
        "embed": _axis_if(model.d_model, dsz, "data") if fsdp else None,
        "embed_act": None,          # activations' embed dim stays unsharded
        "heads": heads_ax,
        "kv_heads": kv_heads_ax,
        "head_dim": None,
        "ffn": _axis_if(model.d_ff, msz, "model"),
        "vocab": _axis_if(model.vocab_size, msz, "model"),
        "vocab_table": _axis_if(model.vocab_size, msz, "model"),
        "experts": _axis_if(model.moe.num_experts, dsz, "data"),
        "experts_router": None,
        "capacity": None,
        "d_inner": _axis_if(model.ssm.expand * model.d_model, msz, "model"),
        "ssm_state": None,
        "ssm_heads": _axis_if(model.ssm.num_heads, msz, "model"),
        "conv": None,
        "codebooks": None,
        "layers": None,
        "units": None,
    }
    return rules


def logical_spec(dims, rules) -> PartitionSpec:
    """Build a PartitionSpec for an *activation* given logical dim names."""
    used, axes = set(), []
    for d in dims:
        ax = rules.get(d) if d is not None else None
        if ax is None:
            axes.append(None)
            continue
        ax_t = (ax,) if isinstance(ax, str) else tuple(ax)
        ax_t = tuple(a for a in ax_t if a not in used)
        if not ax_t:
            axes.append(None)
        else:
            used.update(ax_t)
            axes.append(ax_t[0] if len(ax_t) == 1 else ax_t)
    return PartitionSpec(*axes)
