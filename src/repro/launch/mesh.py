"""Production mesh builders.

`make_production_mesh` is a FUNCTION (not a module constant) so importing this
module never touches jax device state.  The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
tests and benches see the 1 real CPU device.

Mesh semantics (DESIGN.md §2):
  pod    — cross-pod axis (slow ICI/DCN links).  TRINE's "subnetwork" axis:
           the hierarchical collectives minimize stages crossing it.
  data   — intra-pod FSDP/data-parallel axis (the SWMR/SWSR "memory chiplet"
           axis: parameters live sharded here, all-gathered for compute,
           gradients reduce-scattered back).
  model  — tensor-parallel axis (compute chiplets).
"""

from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    # Auto axes: the model places activations with `with_sharding_constraint`,
    # which refuses the Explicit axes `jax.make_mesh` builds by default.
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for unit tests (requires xla_force_host_platform_device_count
    set by the test runner via subprocess env)."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def has_pod_axis(mesh) -> bool:
    return "pod" in mesh.axis_names
