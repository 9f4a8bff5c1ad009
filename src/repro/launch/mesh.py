"""Device meshes: one silo per device index (``make_silo_mesh``), or a
small ("data", "model") mesh over the local devices.

Defined as FUNCTIONS so importing this module never touches jax device
state — ``launch/train.py`` sets ``xla_force_host_platform_device_count``
on the CPU before any jax initialization.
"""

from __future__ import annotations

import jax


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis in Auto (GSPMD) mode: the step
    functions shard through ``NamedSharding``/``shard_map``, not through
    explicit-sharding types, which ``jax.make_mesh`` defaults to."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_silo_mesh(n_silos: int, axis: str = "data") -> jax.sharding.Mesh:
    """1-D mesh hosting one silo per device index.

    Elastic membership sizes this to the *active* silo count, which may
    be (and after churn usually is) smaller than the device universe
    fixed at process start (``xla_force_host_platform_device_count`` on
    CPU, the physical slice on TPU): ``jax.make_mesh`` takes the first
    ``n_silos`` devices and the rest idle until silos rejoin."""
    n = len(jax.devices())
    if not (1 <= n_silos <= n):
        raise ValueError(f"need 1 <= n_silos <= {n} devices, got {n_silos}")
    return _auto_mesh((n_silos,), (axis,))


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over the locally available devices (CPU tests/examples)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return _auto_mesh((data, model), ("data", "model"))

