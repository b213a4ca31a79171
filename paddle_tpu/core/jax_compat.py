"""The repo's one spelling of ``jax.shard_map`` (used by
ops/pallas_kernels, distributed/pipelining, jit/train_step,
jit/serving_step) so the defaults cannot drift between call sites.
"""
from __future__ import annotations

import jax


def shard_map_compat(f, mesh, in_specs, out_specs, manual_axes=None,
                     check=False):
    """``jax.shard_map`` with this repo's defaults.

    manual_axes: set of axis names to run manually (None = all axes).
    check: run the vma/replication checker (off by default: pallas_call
    outputs carry no vma annotation, which the checker refuses to
    guess).
    """
    return jax.shard_map(f, mesh=mesh,
                         axis_names=set(manual_axes or mesh.axis_names),
                         in_specs=in_specs, out_specs=out_specs,
                         check_vma=check)
