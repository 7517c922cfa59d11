"""Trace-time Pallas execution-mode override.

Pallas kernels compile for TPU and run in interpreter mode elsewhere.
"Elsewhere" must be judged by the backend the surrounding jit actually
targets, not the process default: on a machine whose default backend is
TPU, a trainer built with ``dev = cpu`` traces its step for CPU, and a
kernel that consulted ``jax.default_backend()`` would wrongly pick the
compiled path. The layer code knows its target platform (the trainer's
mesh) and pins it here around the op call; ``interpret=...`` is bound at
trace time, so a plain context manager suffices.
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax

_FORCE: Optional[bool] = None


@contextlib.contextmanager
def interpret_mode(force: Optional[bool]):
    """Within the context, pallas ops use ``force`` for interpret=...;
    None defers to the default-backend heuristic."""
    global _FORCE
    prev = _FORCE
    _FORCE = force
    try:
        yield
    finally:
        _FORCE = prev


def interpret() -> bool:
    """Should pallas_call run in interpreter mode (trace-time check)?"""
    if _FORCE is not None:
        return _FORCE
    return jax.default_backend() != "tpu"


# shard_map kwargs that turn its replication checker off: pallas_call
# outputs carry no varying-mesh-axes annotation, so any shard_map body
# that may run a Pallas kernel needs the checker off.
SHARD_MAP_NOCHECK = {"check_vma": False}


def per_shard(mesh, fn, in_specs, out_specs):
    """``fn`` run on each device's shard: the form a Pallas kernel must
    take inside a program that spans several devices. XLA partitions
    its own ops across a mesh but refuses a Mosaic kernel ("Mosaic
    kernels cannot be automatically partitioned. Please wrap the call
    in a shard_map"), and only the TPU compiler says so — interpret
    mode and the XLA twins on a CPU mesh partition fine. ``mesh`` None
    or of one device returns ``fn`` itself."""
    if mesh is None or mesh.size == 1:
        return fn
    from jax import shard_map
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, **SHARD_MAP_NOCHECK)


def rows_spec(mesh, axis: str = "data"):
    """PartitionSpec of an array whose leading dim is rows/batch split
    over ``axis`` (replicated over every other mesh axis); fully
    replicated where the mesh has no such axis."""
    from jax.sharding import PartitionSpec as P
    if mesh is not None and mesh.shape.get(axis, 1) > 1:
        return P(axis)
    return P()
