"""Trace-time mesh context.

The model is parallelism-blind (the reference's load-bearing property,
SURVEY.md §1): it never receives a mesh. Most ops need none — GSPMD
partitions plain jnp from the in/out shardings alone. The exception is the
Pallas flash kernel: a ``pallas_call`` is opaque to the SPMD partitioner, so
without help XLA replicates it (all-gathering q/k/v to every device — the
"replication cliff" on a DP/FSDP/TP mesh).

The trainer publishes its mesh here while tracing the step; the attention
dispatch (``ops/attention.py``) reads it and wraps the kernel in a
``shard_map`` over the batch (``data`` x ``fsdp``) and heads (``tensor``)
axes — attention is independent along both, so the kernel runs unchanged on
each shard. The same pattern as ``ops/ring.py``'s sequence-parallel context,
for the non-sequence axes.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional

from jax.sharding import Mesh


@dataclasses.dataclass(frozen=True)
class MeshContext:
    mesh: Optional[Mesh]


_ACTIVE: Optional[MeshContext] = None


@contextlib.contextmanager
def mesh_scope(mesh: Optional[Mesh]):
    """While active (static, trace-time), mesh-aware ops may shard_map
    themselves over ``mesh`` instead of appearing opaque to GSPMD.
    ``mesh_scope(None)`` masks an outer scope if a region ever needs to
    hide the mesh from nested ops (no current caller does: the pipeline
    stage body is *partial*-manual over {stage, sequence} only, and ops
    that must behave differently inside it key off
    ``ring.current_manual_context()`` instead)."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = MeshContext(mesh)
    try:
        yield
    finally:
        _ACTIVE = prev


def current_mesh() -> Optional[Mesh]:
    return _ACTIVE.mesh if _ACTIVE is not None else None


def kernel_manual_axes(mesh, used) -> set:
    """Mesh axes a Pallas kernel's ``shard_map`` goes manual over.

    Mosaic refuses a kernel under a PARTIAL-manual region ("cannot be
    automatically partitioned"): every mesh axis has to be manual where the
    ``pallas_call`` lowers. So the size-1 axes — nothing can be sharded
    over them, making them manual changes no program — join the axes the
    wrapper actually shards. Axes an enclosing ``shard_map`` already made
    manual (the pipeline stage body) stay out; they count as manual there.
    """
    from jax.sharding import get_abstract_mesh

    idle = {a for a in mesh.axis_names if mesh.shape[a] == 1}
    return (set(used) | idle) - set(get_abstract_mesh().manual_axes)
