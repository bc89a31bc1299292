"""Mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so that
importing this module never touches jax device state.  The production
meshes are:

  single-pod : (16, 16)     axes ("data", "model")   = the paper's (pr, pc)
  multi-pod  : (2, 16, 16)  axes ("pod", "data", "model")

For BFS the ("data", "model") axes play the roles of the paper's processor
(row, column) grid; the "pod" axis batches independent BFS roots.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType

# BFS axis-name aliases: the paper's pr x pc grid mapped onto the mesh.
ROW_AXIS = "data"    # pr: processor rows   (expand/allgather axis)
COL_AXIS = "model"   # pc: processor cols   (fold/alltoall + rotation axis)
POD_AXIS = "pod"


def _mesh(shape, names):
    """A mesh over the first prod(shape) local devices, laid out by
    ``jax.make_mesh`` so device order follows the physical topology (a
    reshape of ``jax.devices()`` in list order need not follow the
    torus).  Axes stay ``Auto``: every program here places its data with
    explicit shard_map specs."""
    need = int(np.prod(shape))
    devs = jax.devices()
    if need > len(devs):
        raise ValueError(f"mesh {'x'.join(map(str, shape))} needs {need} "
                         f"devices, have {len(devs)}")
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devs[:need])


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(pr: int, pc: int, pods: int = 1):
    """An arbitrary rectangular grid (the paper's generalization)."""
    if pods > 1:
        return _mesh((pods, pr, pc), (POD_AXIS, ROW_AXIS, COL_AXIS))
    return _mesh((pr, pc), (ROW_AXIS, COL_AXIS))


def make_local_mesh(pr: int = 1, pc: int = 1, pods: int = 0):
    """Mesh over however many devices this process actually has.
    ``pods > 0`` prepends a pod axis of that size (pods=1 costs no extra
    devices and enables ``BFSEngine.run_batch``)."""
    if pods > 0:
        return _mesh((pods, pr, pc), (POD_AXIS, ROW_AXIS, COL_AXIS))
    return _mesh((pr, pc), (ROW_AXIS, COL_AXIS))


def make_local_mesh_1d(p: int = 1, pods: int = 0):
    """Single-axis mesh for the 1D row decomposition (axis name ROW_AXIS,
    matching the default ``row_axis`` the BFS driver shards over).
    ``pods > 0`` prepends a pod axis for pod-batched multi-source runs —
    the 1D counterpart of the multi-pod 2D mesh."""
    if pods > 0:
        return _mesh((pods, p), (POD_AXIS, ROW_AXIS))
    return _mesh((p,), (ROW_AXIS,))
