"""Production meshes (DESIGN.md Section 4).

Defined as functions, not module constants, so importing this module never
touches jax device state.  The single-pod mesh is a TPU v5e pod slice
(16 x 16 = 256 chips); the multi-pod mesh adds a leading "pod" axis
(2 x 16 x 16 = 512 chips) whose collectives ride the inter-pod DCN/ICI
links.  Axis roles:

  pod    outer data parallelism (+ compressed cross-pod gradient reduce)
  data   data parallelism within a pod
  model  tensor / expert / sequence parallelism
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # jax.make_mesh defaults to Explicit axes; the models place data with
    # with_sharding_constraint, which needs Auto (GSPMD-propagated) axes
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for tests (requires xla_force_host_platform_device_count)."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
