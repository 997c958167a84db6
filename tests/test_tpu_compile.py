"""Main-path Pallas kernels compile for a described TPU v5e chip.

Mosaic checks block shapes, VMEM use and index maps only when it compiles
for a real TPU; interpret mode accepts kernels the chip refuses. These tests
compile each kernel at the paper's widths for a ``v5e:2x2`` topology that is
described, not attached (nothing runs), for every tile candidate the
autotuner may pick, and assert that the Mosaic kernel is in the executable:

  * ``dp_clip``     B = 32, D = 155,530, plain and vmapped over 64 clients;
  * ``l1_distance`` M = 64, D = 155,530;
  * ``dp_round``    B = 32, F = 15552, C = 10, plain and vmapped over 64.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.
"""
import os

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
