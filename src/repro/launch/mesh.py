"""Production meshes.

Single pod: 16 x 16 = 256 chips (data x model).
Multi-pod: 2 x 16 x 16 = 512 chips (pod x data x model); the ``pod``
axis carries only data parallelism (gradient all-reduce over DCI), the
in-pod axes are unchanged -- so the multi-pod dry-run proves the pod
axis shards without touching the in-pod layout.

``make_production_mesh`` is a function (never module-level state): the
dry-run adds --xla_force_host_platform_device_count=512 to XLA_FLAGS
before jax initializes its backend, and importing this module must not
lock device state.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np

SERVE_AXIS = "serve"


def make_mesh_auto(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)


def batch_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """Mesh axes that carry data parallelism."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_smoke_mesh() -> jax.sharding.Mesh:
    """1-device mesh with production axis names (CPU tests)."""
    return make_mesh_auto((1, 1), ("data", "model"))


def make_serve_mesh(num_shards: Optional[int] = None,
                    axis: str = SERVE_AXIS) -> jax.sharding.Mesh:
    """1-D mesh for the sharded serving scheduler.

    Each device along the ``serve`` axis owns one scheduler shard: its
    own slot range, page-pool arena blocks, fault map and governor
    setpoint.  ``num_shards`` defaults to every visible device.  CPU
    smoke runs fan out with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before
    any jax import, like the production dry-runs).
    """
    devices = jax.devices()
    if num_shards is None:
        num_shards = len(devices)
    if not 1 <= num_shards <= len(devices):
        raise ValueError(
            f"make_serve_mesh(num_shards={num_shards}): need 1 <= "
            f"num_shards <= {len(devices)} visible devices (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count before "
            "importing jax to fan out on CPU)")
    return jax.sharding.Mesh(np.asarray(devices[:num_shards]), (axis,))
