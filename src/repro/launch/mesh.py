"""Production meshes. Functions, not module constants — importing this
module must never touch jax device state (the dry-run sets the 512-device
XLA flag before any jax initialization)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: sharding stays a compiler
    decision that the models steer with ``dist.sharding.constraint``
    (jax's default ``Explicit`` axes would make every op's operand
    shardings part of its type)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model). Multi-pod: 2 pods of
    256 = 512 chips (pod, data, model); the pod axis carries pure data
    parallelism (gradient reduction only — the slow DCN hop)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_devices: int | None = None, model: int = 1):
    """Small mesh over locally visible devices (tests / examples)."""
    n = n_devices or len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
