"""The one `jax.shard_map` spelling every call site shares (executor
lowering, flash attention SPMD entry, calibration probes).

Replication (vma) checking is disabled: it cannot see through a
pallas_call's out_shape, and our call sites declare exact specs.
"""

from __future__ import annotations

import jax


def shard_map_compat(f, mesh, in_specs, out_specs):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
