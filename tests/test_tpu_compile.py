"""Main-path Pallas kernels compile for a described TPU v5e chip.

Mosaic checks block shapes, VMEM use and index maps only when it compiles
for a real TPU; interpret mode accepts kernels the chip refuses. These tests
compile each kernel at the paper's widths for a ``v5e:2x2`` topology that is
described, not attached (nothing runs), for every tile candidate the
autotuner may pick, and assert that the Mosaic kernel is in the executable:

  * ``dp_clip``     B = 32, D = 155,530, plain and vmapped over 64 clients;
  * ``l1_distance`` M = 64, D = 155,530;
  * ``dp_round``    B = 32, F = 15552, C = 10, plain and vmapped over 64;
  * ``flash_attention`` forward and backward at the language-model cell's
    widths (s = 8,192, 32 query and 4 KV heads of 128), full and sliding
    (window 1,024), and inside a small language model's DP step, where
    ``obs.hlo_layers`` must map each kernel's custom call to ``attention``.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch
from repro.kernels.dp_clip import ops as dp_ops
from repro.kernels.dp_round import ops as dpr_ops
from repro.kernels.l1_distance import ops as l1_ops

B, D, M, F, C = 32, 155530, 64, 15552, 10


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables can be written to the persistent cache
    # but never read back: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tb,td", dispatch._dp_clip_candidates(B, D))
def test_dp_clip_compiles(one_chip, tb, td):
    _assert_mosaic(lambda x: dp_ops.clip_accumulate_flat(
        x, 1.0, denom=float(B), interpret=False, tb=tb, td=td),
        _spec(one_chip, (B, D)))


@pytest.mark.parametrize("tb,td", dispatch._dp_clip_candidates(B, D))
def test_dp_clip_vmapped_over_clients_compiles(one_chip, tb, td):
    _assert_mosaic(jax.vmap(lambda x: dp_ops.clip_accumulate_flat(
        x, 1.0, denom=float(B), interpret=False, tb=tb, td=td)),
        _spec(one_chip, (M, B, D)))


@pytest.mark.parametrize("tm,td", dispatch._l1_candidates(M, D))
def test_l1_distance_compiles(one_chip, tm, td):
    _assert_mosaic(lambda x: l1_ops.pairwise_l1(x, interpret=False, tm=tm,
                                                td=td),
                   _spec(one_chip, (M, D)))


def _dp_round(tf):
    return lambda p, x, y: dpr_ops.dp_round_linear(p, x, y, clip=1.0, tf=tf,
                                                   interpret=False)


@pytest.mark.parametrize("tf", [t for (t,) in dispatch._dp_round_candidates(F)])
def test_dp_round_compiles(one_chip, tf):
    params = {"w": _spec(one_chip, (F, C)), "b": _spec(one_chip, (C,))}
    _assert_mosaic(_dp_round(tf), params, _spec(one_chip, (B, F)),
                   _spec(one_chip, (B,), jnp.int32))


@pytest.mark.parametrize("tf", [t for (t,) in dispatch._dp_round_candidates(F)])
def test_dp_round_vmapped_over_clients_compiles(one_chip, tf):
    params = {"w": _spec(one_chip, (M, F, C)), "b": _spec(one_chip, (M, C))}
    _assert_mosaic(jax.vmap(_dp_round(tf)), params,
                   _spec(one_chip, (M, B, F)),
                   _spec(one_chip, (M, B), jnp.int32))


# the language-model cell's attention (Mellum2: 32 query, 4 KV heads of 128)
N, S, HQ, KVH, HD = 1, 8192, 32, 4, 128


def _custom_calls(text):
    """(instruction name, kernel name) of each Mosaic call in HLO text."""
    out = []
    for line in text.splitlines():
        if "tpu_custom_call" in line:
            m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s*=", line)
            if m:
                out.append((m.group(1), m.group(1).rsplit(".", 1)[0]))
    return out


@pytest.mark.parametrize("window", [0, 1024], ids=["full", "sliding"])
def test_flash_attention_compiles(one_chip, window):
    from repro.kernels.flash_attention import ops as fl_ops

    def fwd_bwd(q, k, v):
        o, vjp = jax.vjp(lambda *a: fl_ops.flash_attention(
            *a, window=window, interpret=False), q, k, v)
        return vjp(o)

    q = _spec(one_chip, (N, S, HQ, HD))
    k = _spec(one_chip, (N, S, KVH, HD))
    text = jax.jit(fwd_bwd).lower(q, k, k).compile().as_text()
    names = sorted(kernel for _, kernel in _custom_calls(text))
    assert names == ["flash_dkv", "flash_dq", "flash_fwd"]


def test_lm_attention_kernels_map_to_the_attention_layer(one_chip,
                                                          monkeypatch):
    """A small language model's DP step (``dp_layer_clipped_sum``: one
    forward, both backwards under the layer checkpoint) on the Pallas
    backend: every forward, dq and dk/dv kernel call is in the op -> layer
    table under ``attention``, so ``attention_share`` counts the kernels."""
    from repro.config import KernelConfig
    from repro.core import dp
    from repro.core.lm import lm_config, make_lm
    from repro.models.module import abstract_params
    from repro.obs.layers import hlo_layers
    monkeypatch.setattr(dispatch, "_PALLAS_PLATFORMS", ("tpu", "cpu"))
    cfg = lm_config(dict(
        num_layers=2, d_model=256, num_heads=8, num_kv_heads=1, head_dim=128,
        d_ff=128, vocab_size=512, window=256, layer_pattern=["sliding", "full"],
        moe=dict(num_experts=8, experts_per_token=2, held_count=4)))
    specs, apply = make_lm(cfg, KernelConfig(backend="pallas"))
    params = {k: _spec(one_chip, v.shape)
              for k, v in abstract_params(specs).items()}

    def step(p, t):
        total, _ = dp.dp_layer_clipped_sum(
            apply.layer_forward, p, t, lambda z: 1e-3 * jnp.ones_like(z),
            clip=1.0, denom=2.0)
        return total

    text = jax.jit(step).lower(
        params, _spec(one_chip, (2, 512), jnp.int32)).compile().as_text()
    table = hlo_layers(text)
    calls = [(name, kernel) for name, kernel in _custom_calls(text)
             if kernel.startswith("flash_")]
    # dq and dk/dv in each of the two backwards, for each of the two
    # layers, beside the forwards and their recomputations
    kinds = [kernel for _, kernel in calls]
    assert kinds.count("flash_dq") == kinds.count("flash_dkv") == 4
    assert kinds.count("flash_fwd") >= 6
    for name, _ in calls:
        assert "attention" in table[name], (name, table.get(name))
