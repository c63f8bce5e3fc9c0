"""Production mesh definitions (TPU v5e-class pods).

Functions, not module-level constants — importing this module never touches
jax device state (critical because ``xla_force_host_platform_device_count``
must be set before first jax init; see launch/dryrun.py).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD propagation —
    the sharding mode every mesh in this repo is written for)."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Trivial 1-device mesh for tests / smoke runs on CPU."""
    return make_mesh((1, 1), ("data", "model"))


def make_serving_mesh(n_shards: int):
    """1-axis 'model' mesh for SPMD pooled serving: the continuous-batching
    scheduler shards the KV pool's *capacity* dim over it and runs the
    resident decode step as flash-decoding (partial softmax per shard, one
    psum). On CPU boxes, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=<n>`` BEFORE any jax
    import to fake the devices (launch/serve.py --mesh documents this)."""
    require_devices(n_shards)
    return make_mesh((n_shards,), ("model",))


def require_devices(n: int) -> None:
    have = len(jax.devices())
    if have < n:
        raise RuntimeError(
            f"mesh needs {n} devices but only {have} present. For the "
            "dry-run, set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} BEFORE any jax import (launch/dryrun.py does this)."
        )
