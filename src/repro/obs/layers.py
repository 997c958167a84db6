"""Named layers of the co-training round step, and the op -> layer table.

The round step enters each of its layers under ``jax.named_scope`` through
``layer(name)``. A scope is trace-time metadata only: XLA keeps the scope
path in every HLO instruction's ``op_name`` (``jit(run)/while/body/
local_update/vmap(private_grad)/...``), and the compiled program, its
buffers and the engine's chunk-cache keys are those of an unscoped trace.

=====================  =====================================================
layer                  entered around
=====================  =====================================================
``sample_batches``     ``engine.schedule.sample_client_batches`` and the
                       sharded and paged contexts' ``sample_batches``
                       (every round body)
``local_update``       the strategy's local update, entered by the layout
                       context (``engine.schedule.LayoutCtx``) the engine
                       hands the round body
``private_grad``       the private model's gradient in
                       ``P4Trainer._client_step``; on the stacked affine
                       step (``P4Trainer._affine_step``) its logit gradient
                       dl_priv and the closed form (Σ dl_priv, xᵀ dl_priv),
                       with no forward of its own
``proxy_dp_grad``      the proxy model's DP gradient in ``_client_step``
``metrics_step``       the final ``lr = 0`` ``_client_step`` of
                       ``P4Trainer._local_round_keyed``; on the stacked
                       affine step, the one end-of-step forward of both
                       updated models
``per_example_grads``  the per-example ``vmap`` of the gradient and its
                       flatten to the (c, D) stack in ``core.dp.dp_gradients``;
                       the per-row ‖x‖² in ``core.dp.dp_affine_flat``
``dp_clip``            ``kernels.dispatch.clip_accumulate`` / ``dp_clip``;
                       the closed form's norms, scales and the proxy's
                       contraction xᵀ(s ⊙ dl)
``dp_noise``           the flat Eq. 11 noise draw and add
``aggregate``          the strategy's aggregation with the
                       ``merge_participation`` calls around it, entered by
                       the layout context
=====================  =====================================================

``op_layers()`` turns the scopes into a table: for the chunk executable the
engine dispatched last, each HLO instruction name (the name a device trace
prints, ``fusion.477``) maps to the tuple of layers on its ``op_name`` path,
outermost first. It reads the compiled module's own text, so the names are
the executable's. The engine records a chunk's traced signature once, at
its first dispatch (``note_dispatch``); the table is built on demand from
it, cached, and building it compiles nothing and leaves every probe as it
found it.
"""
from __future__ import annotations

import re
import weakref
from typing import Dict, List, Optional, Tuple

import jax

from repro.obs.probes import REGISTRY

LAYERS = ("sample_batches", "local_update", "private_grad", "proxy_dp_grad",
          "metrics_step", "per_example_grads", "dp_clip", "dp_noise",
          "aggregate")
_LAYER_SET = frozenset(LAYERS)


def layer(name: str):
    """``jax.named_scope(name)`` for one of ``LAYERS``; any other name
    raises, so a misspelt scope cannot silently split a layer."""
    if name not in _LAYER_SET:
        raise ValueError(f"unknown layer {name!r}; the round step's layers "
                         f"are {LAYERS}")
    return jax.named_scope(name)


# ---------------------------------------------------------------- op_name

# JAX wraps a scope entered under a transformation in the transform's name:
# ``vmap(metrics_step)``, ``transpose(jvp(per_example_grads))``. A layer
# matches as a whole path segment or inside such wrappers, never as the
# name of a jitted function (``jit(aggregate)`` is a function, not a scope).
_TOKEN = re.compile(r"[A-Za-z_][\w.\-]*")
_FUNCTION_WRAPPERS = ("jit", "pjit")


def path_layers(op_name: str) -> Tuple[str, ...]:
    """The layers on one ``op_name`` path, outermost first, each once."""
    out: List[str] = []
    for m in _TOKEN.finditer(op_name):
        word, s, e = m.group(0), m.start(), m.end()
        if word not in _LAYER_SET or word in out:
            continue
        before = op_name[s - 1] if s else "/"
        after = op_name[e] if e < len(op_name) else "/"
        if before not in "/(" or after not in "/)":
            continue
        if before == "(":
            head = _TOKEN.findall(op_name[:s - 1].rsplit("/", 1)[-1])
            if head and head[-1] in _FUNCTION_WRAPPERS:
                continue
        out.append(word)
    return tuple(out)


# --------------------------------------------------------------- HLO text

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*[({].*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")


def hlo_layers(text: str) -> Dict[str, Tuple[str, ...]]:
    """Instruction name -> layers, from an HLO module's text.

    An instruction with an ``op_name`` takes the layers on its path (an
    empty tuple when none is on it). One without (XLA's layout copies and
    the like) takes the layers of its nearest operand that has an
    ``op_name``, following operands that have none, or failing that of its
    nearest such user. Instructions reached by neither are left out.
    """
    own: Dict[str, Optional[Tuple[str, ...]]] = {}
    operands: Dict[str, List[str]] = {}
    computations = set()
    body: List[Tuple[str, str]] = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computations.add(c.group(1))
            continue
        name, rest = m.group(1), m.group(2)
        meta = rest.find("metadata={")
        op = _OP_NAME.search(rest, meta) if meta >= 0 else None
        own[name] = None if op is None else path_layers(op.group(1))
        body.append((name, rest if meta < 0 else rest[:meta]))
    for name, rest in body:
        operands[name] = [r for r in _REF.findall(rest)
                          if r in own and r not in computations and r != name]
    users: Dict[str, List[str]] = {}
    for name, ops in operands.items():
        for o in ops:
            users.setdefault(o, []).append(name)

    def nearest(start: str, edges: Dict[str, List[str]]):
        seen, frontier = {start}, list(edges.get(start, ()))
        while frontier:
            nxt = []
            for n in frontier:
                if n in seen:
                    continue
                seen.add(n)
                if own.get(n) is not None:
                    return own[n]
                nxt.extend(edges.get(n, ()))
            frontier = nxt
        return None

    table: Dict[str, Tuple[str, ...]] = {}
    for name, layers in own.items():
        if layers is None:
            layers = nearest(name, operands)
            if layers is None:
                layers = nearest(name, users)
        if layers is not None:
            table[name] = layers
    return table


# ------------------------------------------------- dispatched executables

class _Chunk:
    __slots__ = ("traced", "table")

    def __init__(self, traced):
        self.traced = traced
        self.table: Optional[Dict[str, Tuple[str, ...]]] = None


_CHUNKS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_LAST: List[Optional[_Chunk]] = [None]


def note_dispatch(fn, args) -> None:
    """Called by the engine right before it runs the chunk ``fn``. The
    first dispatch of a jitted ``fn`` records its traced signature (the
    abstract arguments with their shardings): tracing first and then
    calling shares one trace and one compile with the call, so the record
    costs neither a retrace nor a compile. Every later dispatch is one dict
    hit. A chunk that is not one jitted function (the sharded engine's
    draw-then-shard_map pair) records nothing, and its table is empty."""
    chunk = _CHUNKS.get(fn)
    if chunk is None:
        trace = getattr(fn, "trace", None)
        chunk = _CHUNKS[fn] = _Chunk(None if trace is None else trace(*args))
    _LAST[0] = chunk


def compiled_text(chunk: _Chunk) -> str:
    """The optimized HLO text of the executable ``chunk`` was dispatched
    as. The lowering and the executable come from JAX's in-memory caches
    that the dispatch filled, so nothing is traced or compiled; every probe
    is put back as it was in any case."""
    before = REGISTRY.snapshot()
    try:
        return chunk.traced.lower().compile().as_text()
    finally:
        for name, counters in before.items():
            probe = REGISTRY.get(name)
            probe.clear()
            probe.update(counters)


def op_layers() -> Dict[str, Tuple[str, ...]]:
    """Instruction name -> layers (outermost first) of the chunk executable
    the engine dispatched last; empty before any dispatch. Built on the
    first call for that executable, then cached."""
    chunk = _LAST[0]
    if chunk is None or chunk.traced is None:
        return {}
    if chunk.table is None:
        chunk.table = hlo_layers(compiled_text(chunk))
    return chunk.table
