"""Production mesh construction (DESIGN.md §5).

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; smoke tests and benches see the real single CPU device.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh


def _make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_worker_mesh(tp: int, dp: int = 1) -> Mesh:
    """Mesh for one serving worker replica group (tp-way model parallel)."""
    return _make_mesh((dp, tp), ("data", "model"))


def make_abstract_mesh(shape: Tuple[int, ...],
                       axes: Tuple[str, ...]) -> AbstractMesh:
    """Device-free mesh for sharding-rule evaluation."""
    return AbstractMesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh() -> Mesh:
    """Degenerate 1x1 mesh for CPU tests/examples."""
    return make_worker_mesh(1, 1)


def data_axes_of(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
