"""The jax surface the package pins in one place (twin of
``dask_ml/_compat.py``, reduced to what we need)."""

from __future__ import annotations

import jax

shard_map = jax.shard_map


def shard_map_unchecked(fn, mesh, in_specs, out_specs):
    """shard_map with the replication check disabled.  Needed when an
    out_spec is P() for a value that is replicated by construction (e.g. the
    R factor of a TSQR) but not provably so to the checker."""
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)
