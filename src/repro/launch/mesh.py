"""Production mesh construction.

Functions, not module-level constants, so importing never touches jax device
state. The dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512
*before* any jax import (see launch/dryrun.py); smoke tests and benchmarks
see the real (single) device.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh


def mesh_axis_types(n: int) -> dict:
    """Kwargs marking all `n` mesh axes Auto (the sharding-propagation mode
    every mesh in this repo uses)."""
    return {"axis_types": (AxisType.Auto,) * n}


def compat_shard_map(f, mesh: Mesh, in_specs, out_specs, axis_names=None):
    """jax.shard_map without the replication check (matching the repo's
    manual-collective kernels).

    axis_names — the *manual* axes; None = all mesh axes.
    """
    kwargs = {"check_vma": False}
    if axis_names is not None:
        kwargs["axis_names"] = frozenset(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def make_abstract_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Device-less mesh for PartitionSpec resolution."""
    return AbstractMesh(tuple(shape), tuple(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **mesh_axis_types(len(axes)))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, **mesh_axis_types(len(axes)))


def make_local_mesh(model_parallel: int = 1) -> Mesh:
    """Best-effort mesh over whatever devices exist (tests / examples)."""
    n = jax.device_count()
    mp = model_parallel if n % model_parallel == 0 else 1
    return make_mesh((n // mp, mp), ("data", "model"))


def host_device_grid(mesh: Mesh) -> dict:
    """Telemetry: devices per axis (for launch scripts / logs)."""
    return dict(zip(mesh.axis_names, mesh.devices.shape))
