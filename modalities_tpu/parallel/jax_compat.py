"""The two `jax.shard_map` conventions the parallelism layer shares.

- `shard_map(...)`: `jax.shard_map` with `axis_names` = the MANUAL axes and
  replication checking off by default (the bodies here call Pallas kernels and
  hand-written collectives, whose outputs carry no varying-axes type).
- `manual_axes()`: the axis names bound manually by an enclosing shard_map region
  at trace time.
"""

from __future__ import annotations

import jax


def manual_axes() -> tuple:
    """Axis names bound manually by an enclosing shard_map region (trace time)."""
    return tuple(jax.sharding.get_abstract_mesh().manual_axes)


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=frozenset(), check_vma=False):
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names=frozenset(axis_names),
        check_vma=check_vma,
    )
