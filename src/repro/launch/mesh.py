"""Meshes: the one helper every mesh in the repo is built with.

Functions, never module-level constants — importing this module must not
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).

Topology (TPU v5e): one pod = 16×16 = 256 chips, ``data`` × ``model``;
multi-pod = 2 pods = 512 chips with a leading ``pod`` axis (DCN-connected).
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """Mesh of ``shape`` over the first ``prod(shape)`` of ``devices``
    (default ``jax.devices()``), in the order given, with every axis Auto.

    ``jax.make_mesh`` defaults its axes to ``AxisType.Explicit``, under
    which a gather on a sharded array needs an ``out_sharding`` — the
    routed filter ops are written for Auto axes, where shardings follow
    from ``in_specs``/``out_specs``.  Devices keep their given order so
    meshes of 2 and 4 shards agree on which device holds shard 0 and 1.
    """
    shape, axes = tuple(shape), tuple(axes)
    devices = list(jax.devices() if devices is None else devices)
    need = math.prod(shape)
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)}")
    return Mesh(np.array(devices[:need]).reshape(shape), axes,
                axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if len(jax.devices()) < math.prod(shape):
        raise RuntimeError(
            f"mesh {shape} needs {math.prod(shape)} devices, have "
            f"{len(jax.devices())} — run via launch/dryrun.py (it sets "
            f"xla_force_host_platform_device_count)")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int | None = None):
    """Small mesh for unit tests (8 host devices)."""
    shape = (pod, data, model) if pod else (data, model)
    axes = ("pod", "data", "model") if pod else ("data", "model")
    return make_mesh(shape, axes)
