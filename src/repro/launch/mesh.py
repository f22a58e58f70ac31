"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax initialization).

  single pod : (16, 16)       axes ("data", "model")   = 256 chips
  multi-pod  : (2, 16, 16)    axes ("pod", "data", "model") = 512 chips

The "pod" axis is the DCN (data-center network) dimension; "data" and
"model" are ICI axes within one pod.  Gradient compression and ZeRO-1
moment sharding target "pod" (see repro.dist).

Every mesh here has ``Auto`` axis types: the repo's sharding rules are
GSPMD annotations that the compiler propagates, not shardings carried in
array types (``jax.make_mesh``'s default, ``Explicit``, would make every
reshape and concatenate of a sharded array check divisibility).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_smoke_mesh(shape=None, axes=("data", "model")):
    """Small mesh with production axis names over CPU host devices.

    ``make_smoke_mesh()`` keeps the historical default — ``(1, n)`` over
    every available device — but a requested ``shape``/``axes`` pair is
    honored exactly (using the first ``prod(shape)`` devices), so dist
    tests can run 2/4/8-way and multi-pod smoke shapes like
    ``make_smoke_mesh((2, 2, 2), ("pod", "data", "model"))`` under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    devs = jax.devices()
    if shape is None:
        shape = (1, len(devs))
    shape = tuple(int(s) for s in shape)
    axes = tuple(axes)
    if len(shape) != len(axes):
        raise ValueError(f"shape {shape} / axes {axes} rank mismatch")
    need = math.prod(shape)
    if need > len(devs):
        raise ValueError(f"mesh {shape} needs {need} devices, "
                         f"have {len(devs)} (set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count=N)")
    return _mesh(shape, axes, devices=devs[:need])
