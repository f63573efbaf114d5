"""Mesh construction for the serving and index planes.

Device-count requirements
-------------------------
``make_production_mesh`` describes the 16x16 (single-pod, 256 chips) or
2x16x16 (multi-pod, 512 chips) production topology.  On hosts with fewer
devices — CPU CI, a 1-chip dev box — it no longer crashes: it degrades to
a 1xN mesh over whatever ``jax.devices()`` reports and emits a structured
``MeshFallbackWarning`` so the degradation is visible in logs and CI.

``make_index_mesh`` builds the 1-D ``("index",)`` mesh used by the
mesh-distributed key-space index (``core/mesh_index.py``).  It takes the
first ``n_devices`` of ``jax.devices()``; asking for more devices than
exist raises ``ValueError`` (no silent shrink — an index built for D
devices has D key-space slices baked into its boundary vector).

CPU fallback
------------
On CPU there is normally one device; multi-device runs are simulated with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before jax
initializes).  All meshes here work identically on forced host devices —
this is how the CI mesh lane runs the equivalence suite.

Everything is a function (not a module-level constant) so importing this
module never touches jax device state — required for the dry-run's forced
host platform to initialize first.
"""
from __future__ import annotations

import warnings

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

INDEX_AXIS = "index"

PRODUCTION_SHAPE = (16, 16)
PRODUCTION_AXES = ("data", "model")
MULTI_POD_SHAPE = (2, 16, 16)
MULTI_POD_AXES = ("pod", "data", "model")


class MeshFallbackWarning(UserWarning):
    """Requested topology does not fit the available devices; degraded."""


def _prod(shape) -> int:
    out = 1
    for s in shape:
        out *= int(s)
    return out


def make_production_mesh(*, multi_pod: bool = False):
    """Production mesh, degrading to 1xN when devices are scarce.

    Returns the 16x16 single-pod (256 chips) or 2x16x16 multi-pod
    (512 chips) mesh when that many devices exist.  Otherwise falls back
    to a 1xN ``("data", "model")`` mesh over all available devices and
    warns with :class:`MeshFallbackWarning` — callers that must not run
    degraded should catch the warning (``warnings.simplefilter("error",
    MeshFallbackWarning)``).
    """
    shape = MULTI_POD_SHAPE if multi_pod else PRODUCTION_SHAPE
    axes = MULTI_POD_AXES if multi_pod else PRODUCTION_AXES
    devices = jax.devices()
    need = _prod(shape)
    if len(devices) >= need:
        return jax.make_mesh(shape, axes,
                             axis_types=(AxisType.Auto,) * len(axes))
    warnings.warn(
        f"mesh fallback: production topology {shape} needs {need} devices "
        f"but only {len(devices)} are available; degrading to a "
        f"1x{len(devices)} ('data', 'model') mesh",
        MeshFallbackWarning, stacklevel=2)
    return Mesh(np.asarray(devices).reshape(1, len(devices)),
                ("data", "model"))


def make_host_mesh():
    """1x1 mesh over the real local device — smoke tests and examples."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_index_mesh(n_devices: int = 0):
    """1-D ``("index",)`` mesh over the first ``n_devices`` devices.

    ``n_devices=0`` (default) uses every available device.  Raises
    ``ValueError`` when more devices are requested than exist: the
    mesh-distributed index bakes one key-space slice per device into its
    boundary vector, so shrinking silently would change the data layout.
    """
    devices = jax.devices()
    if n_devices == 0:
        n_devices = len(devices)
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    if n_devices > len(devices):
        raise ValueError(
            f"make_index_mesh: requested {n_devices} devices but only "
            f"{len(devices)} are available; simulate more with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N")
    return Mesh(np.asarray(devices[:n_devices]), (INDEX_AXIS,))


def validate_index_partition(mesh, total_shards: int) -> int:
    """Check ``total_shards`` divides evenly across the index axis.

    Returns the per-device shard count.  Raises ``ValueError`` with a
    clear message on non-divisible shard-count / mesh-size combinations
    or when the mesh lacks the ``"index"`` axis.
    """
    if INDEX_AXIS not in mesh.axis_names:
        raise ValueError(
            f"mesh has axes {mesh.axis_names}; the distributed index "
            f"requires an '{INDEX_AXIS}' axis (see make_index_mesh)")
    n_dev = int(mesh.shape[INDEX_AXIS])
    if total_shards % n_dev != 0:
        raise ValueError(
            f"total_shards={total_shards} does not divide across "
            f"{n_dev} devices on the '{INDEX_AXIS}' axis; use a shard "
            f"count that is a multiple of the mesh size")
    return total_shards // n_dev


def dp_axes(mesh) -> tuple:
    """Data-parallel mesh axes present in this mesh ('pod' + 'data')."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh) -> int:
    out = 1
    for a in dp_axes(mesh):
        out *= mesh.shape[a]
    return out
