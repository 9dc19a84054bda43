"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and then calls this.

Mesh semantics (DESIGN.md §5):
  * ``pod``   -- data-parallel replicas across pods (gradients cross DCI)
  * ``data``  -- in-pod data parallelism
  * ``model`` -- tensor/expert/sequence parallelism inside a pod
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    # the sharding rules place parameters and leave the compiler to
    # propagate activations; ``jax.make_mesh`` now defaults to Explicit
    # axes, under which every ambiguous gather/scatter/contraction must
    # state its output sharding instead
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh for unit tests (requires >= data*model host devices)."""
    return _auto_mesh((data, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """Axes that carry data parallelism (pod joins data when present)."""
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))
