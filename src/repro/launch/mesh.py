"""Mesh construction: production LM meshes and the PHY cell-serving mesh.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — ``pod`` is the
low-bandwidth inter-pod (DCN) dimension and carries only data-parallel
gradient reductions under the PARAM_RULES in repro.distributed.sharding.

PHY serving uses a (cell, batch) mesh instead: one logical lane per cell,
slots data-parallel within a lane (see :mod:`repro.serve.cell_mesh`).

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import math

import jax


def make_mesh(shape, axes):
    """jax.make_mesh with every axis in Auto sharding mode."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh over the actually-available local devices (tests/examples)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return make_mesh((n // model_axis, model_axis), ("data", "model"))


def make_cell_mesh(n_cells: int):
    """(cell, batch) mesh over the local devices for multi-cell PHY serving.

    The ``cell`` axis gets the largest device-count divisor that also
    divides ``n_cells`` (so every lane group shards evenly); remaining
    devices go to the ``batch`` axis, which data-parallelizes the slots
    within each cell lane.  On one device this degrades to a (1, 1) mesh
    and the serving layer runs unsharded.
    """
    n = len(jax.devices())
    cell = math.gcd(max(n_cells, 1), n)
    return make_mesh((cell, n // cell), ("cell", "batch"))
