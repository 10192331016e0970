"""Production mesh construction.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16) — the pod axis
crosses DCN; data/model stay inside a pod's ICI domain.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before any jax import;
tests run on 1 CPU device).
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: shardings propagate through the compiler, as the
    # dry-run's in/out shardings and shard_map stages expect
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # tests shrink the mesh together with REPRO_DRYRUN_DEVICES, e.g. "2x4"
    # (single pod) / "2x2x2" (multi-pod); production always gets 256/512.
    override = os.environ.get("REPRO_TEST_MESH")
    if override:
        dims = tuple(int(x) for x in override.split("x"))
        if multi_pod and len(dims) == 3:
            shape = dims
        elif not multi_pod and len(dims) == 2:
            shape = dims
    return _auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for subprocess tests (8 fake devices)."""
    return _auto_mesh(shape, axes)
