"""Mesh construction (DESIGN.md §6).

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run must set XLA_FLAGS before first jax init, and tests
must see the real 1-device CPU.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def _mk(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


make_compat_mesh = _mk  # public alias for tests/examples


def ambient_mesh(mesh: Mesh):
    """Context manager installing ``mesh`` as the ambient mesh for
    ``with_sharding_constraint`` during tracing."""
    return jax.set_mesh(mesh)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; multi_pod adds the 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_local_mesh(model_parallel: int = 1) -> Mesh:
    """All locally-visible devices as (data, model) — tests/examples."""
    n = jax.device_count()
    if n % model_parallel:
        raise ValueError(f"{n} local devices not divisible by "
                         f"model_parallel={model_parallel}")
    return _mk((n // model_parallel, model_parallel), ("data", "model"))


def make_elastic_mesh(surviving_slices: int, slice_shape=(16, 16),
                      ) -> Mesh:
    """Re-mesh after failures: rebuild from whole surviving pod slices
    (launch/runtime.py). surviving_slices == 1 degrades to single-pod."""
    if surviving_slices <= 1:
        return _mk(slice_shape, ("data", "model"))
    return _mk((surviving_slices,) + slice_shape, ("pod", "data", "model"))
