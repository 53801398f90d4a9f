"""Device meshes. A FUNCTION, not a constant: importing this module must
never touch jax device state (smoke tests see 1 device; the sharding tests
set XLA_FLAGS=--xla_force_host_platform_device_count in a subprocess).

The multi-pod mesh adds a leading "pod" axis across the DCN.  Axis roles:
  pod   — data parallelism across pods (training grad all-reduce crosses
          DCN) / independent service areas (serving: no cross-pod traffic)
  data  — batch (requests / data-parallel replicas) + FSDP weight sharding
  model — tensor/expert parallelism inside a pod
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the model code places shardings with explicit
    # NamedSharding constraints and shard_map; jax.make_mesh's default
    # Explicit axes would type every array's sharding instead
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *, multi_pod: bool = False):
    """Small mesh for CI-sized sharding tests (8 host devices)."""
    if multi_pod:
        return _mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _mesh((n_data, n_model), ("data", "model"))

