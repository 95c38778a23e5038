"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state.  The CPU-compiled estimates (dry-run, roofline, perf) call
:func:`force_host_devices` first thing in their ``main()``.
"""
from __future__ import annotations

import os

import jax

__all__ = ["make_production_mesh", "make_index_mesh", "data_axes",
           "force_host_devices", "model_axis"]


def force_host_devices(n: int = 512) -> None:
    """Give the CPU backend ``n`` virtual devices, so the production mesh
    can be built and compiled on a host.  Call before JAX touches a device
    (the count is fixed when the backend starts); an ``XLA_FLAGS`` the
    caller already set wins."""
    os.environ.setdefault("XLA_FLAGS",
                          f"--xla_force_host_platform_device_count={n}")


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod ("data", "model"); 2 pods = 512 chips
    ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_index_mesh(n_devices: int | None = None):
    """1-D mesh over the ``items`` axis for the retrieval service's index
    shards: posting tables and item factors partition along it, so catalog
    capacity scales with the device count (single CPU device degrades to a
    trivial mesh and purely logical shards)."""
    n = n_devices or len(jax.devices())
    return jax.make_mesh((n,), ("items",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def data_axes(mesh) -> tuple[str, ...]:
    """Axes the batch dim shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def model_axis(mesh) -> str:
    return "model"
