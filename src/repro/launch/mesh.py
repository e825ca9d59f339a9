"""Production meshes. Functions, not module constants — importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax


def _mesh_kwargs(n):
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi-pod adds a leading 2-pod axis (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_mesh_kwargs(len(axes)))


def _require_devices(fn: str, n: int):
    avail = jax.device_count()
    if n > avail:
        raise ValueError(
            f"{fn}: needs {n} devices but only {avail} XLA device(s) are "
            "visible; on a CPU host set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} in the "
            "environment BEFORE the first jax import")


def make_local_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh for tests (CPU hosts: force host devices via XLA_FLAGS)."""
    _require_devices("make_local_mesh", n_data * n_model)
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         **_mesh_kwargs(2))


def check_stream_sharding(n_shards: int, n_devices: int):
    """Validate the shard-group layout of the device-sharded stream tick."""
    if n_devices < 1:
        raise ValueError(
            f"ShardingSpec.n_devices: must be >= 1, got {n_devices}")
    if n_shards % n_devices != 0:
        raise ValueError(
            f"ShardingSpec.n_devices={n_devices} does not divide "
            f"PoolSpec.n_shards={n_shards}: each device must hold an equal "
            "number of pool shards (pick n_shards a multiple of n_devices)")


def make_stream_mesh(n_devices: int):
    """1-D ``("shard",)`` mesh for the device-sharded labelstream tick.

    CPU hosts get virtual devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before the
    first jax import); on real accelerators the first ``n_devices`` chips
    are used as-is.
    """
    _require_devices("make_stream_mesh", n_devices)
    return jax.make_mesh((n_devices,), ("shard",), **_mesh_kwargs(1))
