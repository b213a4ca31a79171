"""SPMD pipeline parallelism: GPipe-in-HLO over a mesh axis.

The reference implements pipeline parallelism as host-driven per-rank p2p
(python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py:440
1F1B, pp_utils/p2p_communication.py:313 send/recv).  The TPU-native form
compiles the whole schedule into ONE XLA module: every pipeline stage is a
mesh-axis shard, activations move between stages with
``lax.ppermute`` (collective-permute — rides ICI), and the backward
pipeline falls out of ``jax.grad`` reversing the scan, so forward and
backward schedules are both bubble-optimal GPipe without any host round
trips.  (Scaling-book / GSPMD pipelining recipe; no reference analog.)

Also here: ``stack_stage_params`` to build the [n_stages, ...] stacked
parameter pytree that the pipeline shards over the pipe axis.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stack_stage_params(per_stage: Sequence[Dict[str, jax.Array]]
                       ) -> Dict[str, jax.Array]:
    """Stack per-stage pytrees (same structure) into one pytree whose
    leaves have a leading ``n_stages`` dim — the axis sharded over pipe."""
    keys = per_stage[0].keys()
    return {k: jnp.stack([s[k] for s in per_stage], 0) for k in keys}


def spmd_pipeline(stage_fn: Callable, stage_params: Any, xs: jax.Array,
                  *, mesh: Mesh, axis_name: str = "pipe",
                  remat: bool = False) -> jax.Array:
    """Differentiable GPipe forward over ``axis_name``.

    Args:
      stage_fn: ``(local_params, x) -> y`` — one stage's computation on one
        micro-batch; ``y.shape == x.shape`` (hidden-state pipeline).  Runs
        identically on every stage (SPMD); per-stage behavior comes from the
        parameters.
      stage_params: pytree whose leaves are stacked ``[n_stages, ...]`` and
        sharded over ``axis_name`` on dim 0 (other dims may carry tp/fsdp
        shardings — those axes stay in GSPMD-auto mode).
      xs: ``[n_micro, ...]`` micro-batched input, replicated over the pipe
        axis (other axes auto).
    Returns:
      ``[n_micro, ...]`` outputs of the last stage, replicated over pipe.
    """
    n_stages = mesh.shape[axis_name]
    n_micro = xs.shape[0]
    if n_stages == 1:
        f = jax.checkpoint(stage_fn) if remat else stage_fn
        local = jax.tree.map(lambda a: a[0], stage_params)
        return jnp.stack([f(local, xs[i]) for i in range(n_micro)])

    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]
    last = n_stages - 1
    f = jax.checkpoint(stage_fn) if remat else stage_fn

    def pipelined(params, stream):
        local = jax.tree.map(lambda a: jnp.squeeze(a, 0), params)
        idx = lax.axis_index(axis_name)

        mb_shape = stream.shape[1:]
        # initial carries are device-varying (they hold per-stage values)
        def _vary(x):
            return lax.pcast(x, (axis_name,), to="varying")

        state0 = _vary(jnp.zeros(mb_shape, stream.dtype))
        out0 = _vary(jnp.zeros((n_micro,) + mb_shape, stream.dtype))
        pad = jnp.zeros((n_stages - 1,) + mb_shape, stream.dtype)
        feed = jnp.concatenate([stream, pad], 0)   # [T, ...]

        def tick(carry, inp_t):
            state, outputs, t = carry
            # previous stage's activation arrives over ICI
            prev = lax.ppermute(state, axis_name, fwd_perm)
            x_in = jnp.where(idx == 0, inp_t, prev)
            y = f(local, x_in)
            pos = jnp.clip(t - last, 0, n_micro - 1)
            valid = (idx == last) & (t >= last)
            cur = lax.dynamic_index_in_dim(outputs, pos, 0, keepdims=False)
            outputs = lax.dynamic_update_index_in_dim(
                outputs, jnp.where(valid, y, cur), pos, 0)
            return (y, outputs, t + 1), None

        (_, outputs, _), _ = lax.scan(
            tick, (state0, out0, jnp.int32(0)), feed)
        # only the last stage holds real outputs; psum replicates them
        # (backward: cotangents flow to the last stage only, then reverse
        # ppermute drives the backward pipeline)
        return lax.psum(jnp.where(idx == last, outputs,
                                  jnp.zeros_like(outputs)), axis_name)

    param_specs = jax.tree.map(lambda _: P(axis_name), stage_params)
    from ..core.jax_compat import shard_map_compat
    fn = shard_map_compat(pipelined, mesh, in_specs=(param_specs, P()),
                          out_specs=P(), manual_axes={axis_name},
                          check=True)
    return fn(stage_params, xs)


def stage_index_of(layer_idx: int, n_layers: int, n_stages: int,
                   n_chunks: int = 1) -> int:
    """Which pipeline stage owns ``layer_idx`` under (interleaved) uniform
    partitioning: the layer list splits into ``n_stages * n_chunks``
    segments; segment j lives on stage ``j % n_stages`` (chunk ``j //
    n_stages``) — reference pp_layers.py segment->stage mapping with VPP."""
    n_seg = n_stages * n_chunks
    bounds = np.linspace(0, n_layers, n_seg + 1).astype(int)
    seg = int(np.searchsorted(bounds[1:], layer_idx, side="right"))
    return seg % n_stages
