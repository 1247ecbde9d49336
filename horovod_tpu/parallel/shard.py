"""``jax.shard_map`` with varying-manual-axes checking off by default.

Collective-heavy bodies (all_gather outputs consumed as replicated)
frequently defeat the static VMA inference, and the collectives in
``horovod_tpu.ops.collective`` define their own replication semantics, so
``check_vma`` defaults to ``False`` here; pass ``check_vma=True`` to ask
for the check.
"""

from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, *, check_vma: bool = False,
              axis_names=frozenset()):
    """``shard_map(f, mesh, in_specs, out_specs)``.  ``axis_names`` names
    the manual axes of a partial-manual map (default: every mesh axis)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma,
                         axis_names=frozenset(axis_names))
