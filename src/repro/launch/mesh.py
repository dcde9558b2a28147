"""Production meshes.

Single pod: 256 chips as ("data", "model") = (16, 16).
Multi-pod:  512 chips as ("pod", "data", "model") = (2, 16, 16) — the pod
axis carries pure data parallelism (per-pod parameter replicas, gradient
sync over ICI/DCN, optionally roaring-compressed via repro.grad_comp).

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax initialization).
"""

from __future__ import annotations

import jax


def _mesh(shape, axes):
    # Auto axes: shardings propagate through jit and gathers from a sharded
    # array need no explicit output sharding (``jax.make_mesh`` defaults to
    # Explicit axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh: the four chips of one host as (4, 1), or host devices
    for CPU tests (requires >= data*model devices)."""
    return _mesh((data, model), ("data", "model"))
