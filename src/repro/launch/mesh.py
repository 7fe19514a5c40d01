"""Production mesh construction.

A FUNCTION, not a module constant — importing this module never touches jax
device state; the 512-host-device dry-run and the 1-device test environment
coexist (system-prompt contract).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — run "
            "under XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(repro.launch.dryrun sets this automatically)")
    arr = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(arr, axes)


def make_fleet_mesh(n_devices: int = None):
    """1-D ``("data",)`` mesh over the host's devices for fleet/client-axis
    execution (``Engine(mesh=...)``): bucket kernels shard_map their slot
    axis over it, stacked fleet storage shards via
    ``launch.sharding.fleet_pspecs``. ``n_devices=None`` uses every device
    (force a multi-device host with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)."""
    import jax

    devices = jax.devices()
    n = len(devices) if n_devices is None else n_devices
    if len(devices) < n:
        raise RuntimeError(f"fleet mesh wants {n} devices, found "
                           f"{len(devices)}")
    return jax.sharding.Mesh(np.asarray(devices[:n]), ("data",))


def make_test_mesh(shape: Tuple[int, ...] = (2, 2),
                   axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh for unit tests (requires host-device override >= prod)."""
    import jax

    n = int(np.prod(shape))
    arr = np.asarray(jax.devices()[:n]).reshape(shape)
    return jax.sharding.Mesh(arr, axes)


def make_abstract_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Device-free mesh for sharding-rule validation."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(shape), tuple(axes))


def fsdp_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def data_axes(mesh) -> tuple:
    return fsdp_axes(mesh)


TENSOR_AXIS = "model"
