"""jax API spellings shared across call sites."""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with the static replication check OFF by default
    (jax's own default is on): every body here carries manual collectives
    or a pallas_call the check cannot see through."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )
