"""The one shard_map spelling the parallel stack uses.

Replication checking is off: the loss reductions pmean over every mesh
axis themselves, which `check_vma` cannot see through.
"""
from __future__ import annotations

import jax

__all__ = ["shard_map"]


def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
