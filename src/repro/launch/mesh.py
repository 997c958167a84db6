"""Production mesh definitions (v5e) + host-simulation meshes.

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).

Every mesh is built with ``Auto`` axis types. ``jax.make_mesh`` defaults to
``Explicit`` axes, which put the mesh axis into each placed array's type: a
client stack moved off the mesh onto one device would still carry
``@clients``, and vmapping it beside an unsharded array then fails.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

from repro.config import MeshConfig
from repro.sharding.rules import CLIENT_AXIS


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256-chip pod; multi-pod = 2 pods = 512 chips.

    Axes: ``pod`` (= the P4 group axis, DCN), ``data`` (batch/FSDP, ICI),
    ``model`` (tensor parallel, ICI)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(cfg: MeshConfig):
    return _auto_mesh(cfg.shape, cfg.axis_names)


def host_mesh_shape(data: int, model: int, num_devices: int) -> Tuple[int, int]:
    """Explicit clamping for the host-simulation mesh (pure, tested):

      data'  = clamp(data, 1, n)        — never exceed available devices
      model' = clamp(model, 1, n//data') — whatever capacity data left over

    A model request that no longer fits after the data clamp degrades to a
    1-sized model axis (replicated tensor-parallel) instead of crashing on
    ``n // 0`` or silently requesting more devices than exist. The product
    data'·model' is always ≥ 1 and ≤ n."""
    n = max(1, int(num_devices))
    data = max(1, min(int(data), n))
    model = max(1, min(int(model), n // data))
    return data, model


def make_host_mesh(data: int = 1, model: int = 1, *,
                   num_devices: Optional[int] = None):
    """Tiny mesh over real host devices (tests / examples); shapes are the
    explicit ``host_mesh_shape`` clamp, and the mesh only claims the devices
    it uses (the product may be smaller than the device count)."""
    n = num_devices if num_devices is not None else len(jax.devices())
    data, model = host_mesh_shape(data, model, n)
    return _auto_mesh((data, model), ("data", "model"),
                      devices=jax.devices()[: data * model])


def make_client_mesh(clients: Optional[int] = None, *, axis: str = CLIENT_AXIS):
    """1-D mesh over ``clients`` devices for the sharded federation engine
    (``repro.engine.ShardedEngine``): each slice hosts a disjoint client
    shard of the (M, ...) state/data stacks. Default: every host device."""
    n = len(jax.devices())
    clients = n if clients is None else max(1, min(int(clients), n))
    return _auto_mesh((clients,), (axis,), devices=jax.devices()[:clients])
