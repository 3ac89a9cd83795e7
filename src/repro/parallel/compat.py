"""The jax sharding surface the repo calls, in one module.

``parallel/``, ``models/`` and ``train/`` reach ``jax.shard_map``,
``jax.make_mesh`` and the ambient abstract mesh only through here, so the
spelling the repo relies on is written down once.

``shard_map`` takes ``axis_names``: the set of mesh axes the region is
MANUAL over.  Callers prefer manual over ALL mesh axes — the compressed
train-step region runs fully manual, which keeps model compute purely local
and avoids partial-manual partitioning (an XLA-CPU SPMD partitioner check
failure, ``IsManualSubgroup``, in earlier releases).
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, *, axis_names, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` manual over ``axis_names``."""
    return jax.shard_map(
        f,
        mesh=mesh,
        axis_names=frozenset(axis_names),
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=check_vma,
    )


def make_mesh(axis_shapes, axis_names, *, auto_axis_types: bool = False):
    """``jax.make_mesh``; ``auto_axis_types`` marks every axis ``Auto``."""
    if auto_axis_types:
        types = (jax.sharding.AxisType.Auto,) * len(axis_names)
        return jax.make_mesh(axis_shapes, axis_names, axis_types=types)
    return jax.make_mesh(axis_shapes, axis_names)


def get_abstract_mesh():
    """The ambient abstract mesh (empty outside a mesh context)."""
    return jax.sharding.get_abstract_mesh()
