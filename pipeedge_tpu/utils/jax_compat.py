"""The one `shard_map` entry point of this tree.

Every shard_map body here manages its own collectives, so the
replication/VMA check is off everywhere; calling through this wrapper
keeps that decision in one place.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with the replication/VMA check disabled."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
