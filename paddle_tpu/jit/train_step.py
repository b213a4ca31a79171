"""Fully-fused compiled training step.

The TPU-native execution form of SURVEY.md §3.5: forward + backward +
optimizer update traced into ONE XLA module (loss scaling / grad clip
included), with buffer donation so parameters update in place in HBM.
This is what bench.py and __graft_entry__ run; the eager tape remains the
flexible path.

Usage:
    step = TrainStep(model, criterion, optimizer)
    loss = step(batch_inputs, labels)        # one fused XLA call

ZeRO-1/2 sharded weight update (Xu et al., arXiv:2004.13336 "Automatic
Cross-Replica Sharding of Weight Update in Data-Parallel Training"):
pass a mesh + :class:`ShardingConfig` and the SAME donated module
reduce-scatters gradients over the data-parallel axis, applies the
optimizer update to only this replica's 1/dp shard of the parameters and
optimizer state (states are CREATED sharded via ``NamedSharding`` —
never materialized replicated), then all-gathers the updated parameters:

    cfg  = ShardingConfig(stage=2)           # 1 = os, 2 = os_g (ZeRO-2)
    step = TrainStep(model, criterion, opt, mesh=mesh, sharding=cfg)

Optimizer-state HBM per replica drops by the dp degree; stage-2 lowers
the grad sync itself to ONE ``reduce-scatter`` per coalesced bucket
(the same dtype-bucketed flat-buffer layout as the DP-overlap
``coalesce_tensor`` machinery in ``distributed/passes``), instead of a
full-gradient all-reduce.  The sharded step is an explicit SPMD program
(``shard_map``): each replica computes grads on its batch shard, so the
criterion must be batch-separable with a mean (default) or sum
reduction — the standard data-parallel contract.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn.layer_base import Layer, Parameter
from ..optimizer.optimizer import Optimizer
from ..ops import random as _random
# the mesh/axis/spec machinery is shared with the serving steps — one
# SPMD module (jit/spmd.py) is the single source of both; ShardingConfig
# is re-exported here for the existing import sites
from .spmd import (ShardingConfig, SpecLayout, _entry_names,
                   gather_spec_axes, llama_param_specs,
                   resolve_mesh_axis, spec_axes)

__all__ = ["TrainStep", "ShardingConfig"]


class _ParamShim:
    """Duck-typed stand-in so ``optimizer._init_state`` can be traced
    (it only reads ``p._value`` and ``p.name``)."""

    def __init__(self, value, name):
        self._value = value
        self.name = name


class TrainStep:
    """Compile model+criterion+optimizer into one donated-buffer XLA step."""

    def __init__(self, model: Layer, criterion: Callable,
                 optimizer: Optimizer, clip_norm: Optional[float] = None,
                 mesh=None, sharding: Optional[ShardingConfig] = None):
        self.model = model
        self.criterion = criterion
        self.optimizer = optimizer
        self.clip_norm = clip_norm
        # bumped inside the traced body: one bump per (re)trace, so tests
        # can assert the step compiles exactly once across training
        self.compile_count = 0

        sd = model.state_dict()
        self._keys = list(sd.keys())
        self._trainable = [k for k in self._keys
                           if isinstance(sd[k], Parameter)
                           and not sd[k].stop_gradient]
        self._frozen = [k for k in self._keys if k not in self._trainable]
        self._step_fn = None

        # a sharding pass / group_sharded_parallel may have marked the
        # optimizer for the fused sharded path — pick it up so the eager
        # wrapper and the compiled path agree.  An implicit marker must
        # never make a previously-working construction crash: it degrades
        # to the replicated step with a warning instead of raising.
        implicit = False
        if mesh is None and sharding is None:
            marker = getattr(optimizer, "_sharded_update", None)
            if marker is not None:
                mesh, sharding = marker
                implicit = True

        self._sharded = False
        if mesh is not None or sharding is not None:
            try:
                self._setup_sharded(mesh, sharding or ShardingConfig(), sd)
            except (ValueError, NotImplementedError):
                if not implicit:
                    raise
                import warnings
                import sys as _sys
                warnings.warn(
                    f"ignoring the optimizer's _sharded_update marker "
                    f"({_sys.exc_info()[1]}); building the replicated "
                    f"TrainStep instead", stacklevel=2)
                self._sharded = False

        if not self._sharded:
            # optimizer state pytree per trainable param (replicated path)
            self._opt_states = {k: optimizer._ensure_state(sd[k])
                                for k in self._trainable}

    # -- sharded setup -------------------------------------------------------
    def _setup_sharded(self, mesh, cfg: ShardingConfig, sd):
        # 2D (fsdp×tp) mesh (round 21): params/grads/optimizer state
        # live fsdp×tp-sharded end to end — ZeRO-3 as the storage
        # layout, composed with the serving tp placement
        if mesh is not None:
            from ..distributed.process_mesh import as_jax_mesh
            probe = as_jax_mesh(mesh)
            total = 1
            for a in probe.axis_names:
                total *= probe.shape[a]
            # any mesh that names an fsdp axis and has >1 chip takes
            # the 2D path — including fsdp=1 x tp>1, where tp alone is
            # the storage axis (a degenerate-but-valid grid corner)
            if "fsdp" in probe.axis_names and total > 1:
                self._setup_sharded_2d(probe, cfg, sd)
                return
        jmesh, axis, deg = resolve_mesh_axis(
            mesh, cfg.axis, cfg.degree,
            candidates=("dp", "sharding", "data"))
        if deg <= 1:
            return     # degenerate: plain replicated step
        other = [a for a in jmesh.axis_names if a != axis
                 and jmesh.shape[a] > 1]
        if other:
            raise NotImplementedError(
                f"the 1D sharded weight update composes only with pure "
                f"data parallelism; mesh has extra axes {other} — for "
                f"fsdp×tp weight sharding name the storage axis 'fsdp' "
                f"(spmd.mesh_2d) and the 2D path takes over")
        if not getattr(self.optimizer, "shardable_update", True):
            raise ValueError(
                f"{type(self.optimizer).__name__}'s update rule is not "
                f"elementwise (cross-element reductions would be computed "
                f"per shard) — use the replicated TrainStep; its state is "
                f"small anyway")
        self._sharded = True
        self._mode = "1d"
        self._jmesh = jmesh
        self._axis = axis
        self._deg = deg
        self._shard_cfg = cfg
        from jax.sharding import NamedSharding, PartitionSpec
        self._repl = NamedSharding(jmesh, PartitionSpec())
        self._row_sh = NamedSharding(jmesh, PartitionSpec(axis))

        # which params can shard their update: dim0 divisible by the
        # degree AND every array state leaf is param-shaped (elementwise
        # state) — others update replicated on every rank
        self._shardable: Dict[str, bool] = {}
        self._state_shardings: Dict[str, Dict[str, Any]] = {}
        for k in self._trainable:
            p = sd[k]
            shape = tuple(p._value.shape)
            ok = len(shape) >= 1 and shape[0] % deg == 0
            if ok:
                abstract = jax.eval_shape(
                    self._make_state_init(p, k),
                    jax.ShapeDtypeStruct(shape, p._value.dtype))
                for leaf in jax.tree_util.tree_leaves(abstract):
                    if leaf.ndim >= 1 and tuple(leaf.shape) != shape:
                        import warnings
                        warnings.warn(
                            f"param {k!r}: optimizer state leaf of shape "
                            f"{leaf.shape} is not parameter-shaped; its "
                            f"update stays replicated", stacklevel=3)
                        ok = False
                        break
            self._shardable[k] = ok
        self._opt_states = {}
        for k in self._trainable:
            self._refresh_state(k, sd[k])

    def _setup_sharded_2d(self, jmesh, cfg: ShardingConfig, sd):
        """fsdp×tp weight sharding (round 21): every trainable param is
        STORED in its composed family placement (``spmd.SpecLayout``
        with an fsdp axis — ZeRO-3 subsumed as the storage layout, no
        stage knob), optimizer state and grads inherit it, and the
        traced step gathers for compute / reduce-scatters back.  Extra
        mesh axes (a ``dp`` replica axis) are pure batch parallelism:
        the batch shards over EVERY axis and grads reduce over the
        axes a spec does not name."""
        if not getattr(self.optimizer, "shardable_update", True):
            raise ValueError(
                f"{type(self.optimizer).__name__}'s update rule is not "
                f"elementwise (cross-element reductions would be computed "
                f"per shard) — use the replicated TrainStep; its state is "
                f"small anyway")
        from jax.sharding import NamedSharding, PartitionSpec
        self._sharded = True
        self._mode = "2d"
        self._jmesh = jmesh
        self._shard_cfg = cfg
        sizes = dict(jmesh.shape)
        self._axes = tuple(jmesh.axis_names)
        self._deg = 1
        for a in self._axes:
            self._deg *= sizes[a]
        tp_live = sizes.get("tp", 1) > 1
        self._fsdp_deg = sizes["fsdp"]
        self._tp_deg = sizes.get("tp", 1)
        self._repl = NamedSharding(jmesh, PartitionSpec())
        self._row_sh = None              # 1D-path artifact, unused here
        layout = SpecLayout(tp_axis="tp" if tp_live else None,
                            fsdp_axis="fsdp")
        shapes = {k: tuple(sd[k]._value.shape) for k in self._trainable}
        specs = llama_param_specs(self._trainable, layout,
                                  shapes=shapes, mesh=jmesh)
        # shardability: a named spec AND param-shaped (elementwise)
        # optimizer state — a non-param-shaped leaf forces the whole
        # param back to replicated, same contract as the 1D path
        self._shardable: Dict[str, bool] = {}
        self._param_specs: Dict[str, Any] = {}
        self._param_sh: Dict[str, Any] = {}
        self._state_shardings: Dict[str, Dict[str, Any]] = {}
        for k in self._trainable:
            p = sd[k]
            spec = specs[k]
            ok = bool(spec_axes(spec))
            if ok:
                abstract = jax.eval_shape(
                    self._make_state_init(p, k),
                    jax.ShapeDtypeStruct(shapes[k], p._value.dtype))
                for leaf in jax.tree_util.tree_leaves(abstract):
                    if leaf.ndim >= 1 and tuple(leaf.shape) != shapes[k]:
                        import warnings
                        warnings.warn(
                            f"param {k!r}: optimizer state leaf of shape "
                            f"{leaf.shape} is not parameter-shaped; its "
                            f"param stays replicated", stacklevel=3)
                        ok = False
                        break
            if not ok:
                spec = PartitionSpec()
            self._shardable[k] = ok
            self._param_specs[k] = spec
            self._param_sh[k] = NamedSharding(jmesh, spec)
        self._opt_states = {}
        for k in self._trainable:
            self._refresh_state(k, sd[k])
        # observability: the storage-sharding degree this process
        # trains at, plus the static per-dispatch fsdp/tp param-gather
        # payload (counted per step in __call__)
        from ..observability import default_registry
        r = default_registry()
        r.gauge(
            "train_fsdp_degree",
            "fsdp (weight-storage sharding) degree of the most "
            "recently constructed 2D TrainStep in this process "
            "(1 = params replicated)").set(self._fsdp_deg)
        self._m_gather_bytes = r.counter(
            "spmd_allgather_bytes_total",
            "per-chip bytes received by spmd param all-gathers, by "
            "site: the 2D train step's per-step param gather "
            "(train_params) and the sharded serving prologue's fsdp "
            "gather (serving_params)", labels=("site",)
        ).labels(site="train_params")
        self._gather_bytes_per_step = 0
        for k in self._trainable:
            part = 1
            for name in spec_axes(self._param_specs[k]):
                part *= sizes.get(name, 1)
            if part > 1:
                v = sd[k]._value
                nbytes = int(np.prod(shapes[k])) * v.dtype.itemsize
                self._gather_bytes_per_step += \
                    nbytes - nbytes // part

    def _make_state_init(self, p, k):
        opt = self.optimizer
        name = getattr(p, "name", k)
        multi = bool(getattr(opt, "_multi_precision", False))

        def init_fn(pv):
            st = opt._init_state(_ParamShim(pv, name))
            if multi and pv.dtype in (jnp.bfloat16, jnp.float16):
                st["master"] = pv.astype(jnp.float32)
            return st

        return init_fn

    def _leaf_sharding(self, k, p, leaf_shape):
        if self._shardable[k] and len(leaf_shape) >= 1 \
                and tuple(leaf_shape) == tuple(p._value.shape):
            return self._param_sh[k] if getattr(self, "_mode", "1d") \
                == "2d" else self._row_sh
        return self._repl

    def _refresh_state(self, k, p):
        """Bind ``self._opt_states[k]`` to the optimizer's live state dict
        for ``p``, creating it ALREADY SHARDED (jitted init with
        ``out_shardings`` — the replicated tensor never exists) or
        re-placing leaves that lost their sharding (set_state_dict loads
        full host arrays)."""
        opt_state = self.optimizer._state
        st = opt_state.get(id(p))
        if st is not None and st is self._opt_states.get(k):
            # fast path for the hot loop: the step updates this dict in
            # place with already-sharded outputs, so nothing to re-place
            # unless set_state_dict swapped the dict object out
            return
        if st is None:
            init_fn = self._make_state_init(p, k)
            abstract = jax.eval_shape(
                init_fn, jax.ShapeDtypeStruct(p._value.shape,
                                              p._value.dtype))
            out_sh = jax.tree_util.tree_map(
                lambda l: self._leaf_sharding(k, p, l.shape), abstract)
            st = jax.jit(init_fn, out_shardings=out_sh)(p._value)
            opt_state[id(p)] = st
        shardings = {}
        for name, v in st.items():
            if not hasattr(v, "shape"):
                continue
            sh = self._leaf_sharding(k, p, v.shape)
            shardings[name] = sh
            if not (isinstance(v, jax.Array) and v.sharding == sh):
                st[name] = jax.device_put(jnp.asarray(v), sh)
        self._opt_states[k] = st
        self._state_shardings[k] = shardings

    def _place_replicated(self, sd):
        """Params + frozen buffers replicated over the mesh before the
        call, so jit never reshards a donated argument (donation aliases
        from the very first step)."""
        for k in self._trainable + self._frozen:
            v = sd[k]._value
            if not (isinstance(v, jax.Array) and v.sharding == self._repl):
                sd[k]._value = jax.device_put(jnp.asarray(v), self._repl)

    def _place_params_2d(self, sd):
        """2D path: trainable params placed in their fsdp×tp STORAGE
        sharding (the replicated tensor never exists past the first
        placement — ZeRO-3), frozen buffers replicated.  Arrays already
        carrying their sharding (the step's own outputs, or a serving
        tree handed back) are left untouched, so steady state pays an
        equality probe, never a transfer."""
        for k in self._trainable:
            v = sd[k]._value
            sh = self._param_sh[k]
            if not (isinstance(v, jax.Array) and v.sharding == sh):
                sd[k]._value = jax.device_put(jnp.asarray(v), sh)
        for k in self._frozen:
            v = sd[k]._value
            if not (isinstance(v, jax.Array) and v.sharding == self._repl):
                sd[k]._value = jax.device_put(jnp.asarray(v), self._repl)

    # -- traced loss (shared by both paths) ----------------------------------
    def _make_loss_fn(self, frozen_vals, batch, key):
        model, criterion, frozen = self.model, self.criterion, self._frozen

        def loss_fn(p):
            state = dict(p)
            state.update(frozen_vals)
            with model.bind_state(state):
                with _random.trace_rng_scope(key):
                    out = model(*[Tensor._from_value(b)
                                  for b in batch[:-1]])
                    loss = criterion(out,
                                     Tensor._from_value(batch[-1]))
                # collect traced buffer updates (BatchNorm running
                # stats reassign their bound tracer in training
                # mode — F.batch_norm's contract expects the fused
                # step to persist them) BEFORE bind_state restores
                # the originals.  Returned as aux: excluded from
                # the grad but part of the compiled step's outputs.
                new_bufs = {}
                sd = model.state_dict()
                for k in frozen:
                    v = sd[k]._value
                    if v is not state[k]:
                        new_bufs[k] = v
            return loss._value.astype(jnp.float32), new_bufs

        return loss_fn

    # -- replicated build -----------------------------------------------------
    def _build(self):
        opt = self.optimizer
        trainable = self._trainable
        clip_norm = self.clip_norm

        def train_step(params, frozen_vals, opt_states, lr, key, *batch):
            self.compile_count += 1
            loss_fn = self._make_loss_fn(frozen_vals, batch, key)
            (loss, new_bufs), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)

            if clip_norm is not None:
                gnorm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in grads.values()))
                scale = clip_norm / jnp.maximum(gnorm, clip_norm)
                grads = {k: (g * scale).astype(g.dtype)
                         for k, g in grads.items()}

            hyper = {"lr": lr}
            new_params = {}
            new_states = {}
            for k in trainable:
                np_, nst = opt._update_rule(params[k], grads[k],
                                            opt_states[k], hyper)
                new_params[k] = np_
                new_states[k] = nst
            return loss, new_params, new_states, new_bufs

        # donate params + opt states: in-place HBM update
        self._step_fn = jax.jit(train_step, donate_argnums=(0, 2))

    # -- sharded build --------------------------------------------------------
    def _grad_buckets(self):
        """Stage-2 coalesce layout: shardable keys grouped by dtype, then
        packed into buckets of <= bucket_mb — ONE reduce-scatter per
        bucket over the flat (degree, cols) buffer (the coalesce_tensor
        fused-buffer idea applied to the grad sync)."""
        sd = self.model.state_dict()
        budget = int(self._shard_cfg.bucket_mb * 1024 * 1024)
        groups: Dict[str, List[str]] = {}
        nonshard = []
        for k in self._trainable:
            if self._shardable[k]:
                groups.setdefault(str(sd[k]._value.dtype), []).append(k)
            else:
                nonshard.append(k)
        buckets: List[List[str]] = []
        for keys in groups.values():
            cur, cur_bytes = [], 0
            for k in keys:
                v = sd[k]._value
                nbytes = int(np.prod(v.shape)) * v.dtype.itemsize
                if cur and cur_bytes + nbytes > budget:
                    buckets.append(cur)
                    cur, cur_bytes = [], 0
                cur.append(k)
                cur_bytes += nbytes
            if cur:
                buckets.append(cur)
        return buckets, nonshard

    def _build_sharded(self, batch_vals):
        from ..core.jax_compat import shard_map_compat
        from jax.sharding import NamedSharding, PartitionSpec

        opt = self.optimizer
        trainable, frozen = self._trainable, self._frozen
        clip_norm = self.clip_norm
        mesh, axis, deg = self._jmesh, self._axis, self._deg
        cfg = self._shard_cfg
        stage = cfg.stage
        mean_combine = cfg.loss_reduction == "mean"
        shardable = self._shardable
        buckets, nonshard = self._grad_buckets()
        sd0 = self.model.state_dict()
        shapes = {k: tuple(sd0[k]._value.shape) for k in trainable}
        rows = {k: shapes[k][0] // deg for k in trainable if shardable[k]}

        def sync_grads(grads):
            """All grads leave this function mean/sum-combined across
            replicas; shardable keys leave SHARDED (this rank's rows)."""
            out = {}
            for bucket in buckets:
                cols = [int(np.prod(shapes[k])) // deg for k in bucket]
                mat = jnp.concatenate(
                    [grads[k].reshape(deg, -1) for k in bucket], axis=1) \
                    if len(bucket) > 1 else grads[bucket[0]].reshape(deg, -1)
                if stage >= 2:
                    # ZeRO-2: each rank only ever receives its grad shard
                    row = jax.lax.psum_scatter(mat, axis,
                                               scatter_dimension=0,
                                               tiled=False)
                else:
                    # ZeRO-1: full-gradient all-reduce, local row slice
                    full = jax.lax.psum(mat, axis)
                    row = jnp.squeeze(jax.lax.dynamic_slice_in_dim(
                        full, jax.lax.axis_index(axis), 1, 0), 0)
                if mean_combine:
                    row = row / deg
                off = 0
                for k, c in zip(bucket, cols):
                    out[k] = row[off:off + c].reshape(
                        (rows[k],) + shapes[k][1:])
                    off += c
            # non-shardable params: coalesced all-reduce, replicated update
            by_dtype: Dict[str, List[str]] = {}
            for k in nonshard:
                by_dtype.setdefault(str(grads[k].dtype), []).append(k)
            for keys in by_dtype.values():
                flat = jnp.concatenate([grads[k].reshape(-1)
                                        for k in keys]) \
                    if len(keys) > 1 else grads[keys[0]].reshape(-1)
                red = jax.lax.psum(flat, axis)
                if mean_combine:
                    red = red / deg
                off = 0
                for k in keys:
                    n = int(np.prod(shapes[k])) if shapes[k] else 1
                    out[k] = red[off:off + n].reshape(shapes[k])
                    off += n
            return out

        def train_step(params, frozen_vals, opt_states, lr, key, *batch):
            self.compile_count += 1
            idx = jax.lax.axis_index(axis)
            # distinct dropout stream per replica (true-DP semantics)
            loss_fn = self._make_loss_fn(
                frozen_vals, batch, jax.random.fold_in(key, idx))
            (loss, new_bufs), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)

            grads = sync_grads(grads)

            if clip_norm is not None:
                # global grad norm: sharded pieces psum'd, replicated
                # pieces counted once per rank (identical on all ranks)
                local = sum((jnp.sum(jnp.square(
                    grads[k].astype(jnp.float32)))
                    for k in trainable if shardable[k]),
                    jnp.asarray(0.0, jnp.float32))
                total = jax.lax.psum(local, axis) + sum(
                    (jnp.sum(jnp.square(grads[k].astype(jnp.float32)))
                     for k in trainable if not shardable[k]),
                    jnp.asarray(0.0, jnp.float32))
                gnorm = jnp.sqrt(total)
                scale = clip_norm / jnp.maximum(gnorm, clip_norm)
                grads = {k: (g * scale).astype(g.dtype)
                         for k, g in grads.items()}

            hyper = {"lr": lr}
            new_params = {}
            new_states = {}
            for k in trainable:
                if shardable[k]:
                    # update THIS rank's 1/deg rows, then all-gather the
                    # refreshed parameter (the weight-update-sharding
                    # dataflow of arXiv:2004.13336)
                    p_sh = jax.lax.dynamic_slice_in_dim(
                        params[k], idx * rows[k], rows[k], 0)
                    np_, nst = opt._update_rule(p_sh, grads[k],
                                                opt_states[k], hyper)
                    new_params[k] = jax.lax.all_gather(
                        np_, axis, axis=0, tiled=True)
                else:
                    np_, nst = opt._update_rule(params[k], grads[k],
                                                opt_states[k], hyper)
                    new_params[k] = np_
                new_states[k] = nst
            # combine per-replica losses the same way the grads combine,
            # so the reported loss matches the replicated step's
            loss = jax.lax.pmean(loss, axis) if mean_combine \
                else jax.lax.psum(loss, axis)
            # running stats (BN) are averages in either mode
            new_bufs = jax.tree_util.tree_map(
                lambda v: jax.lax.pmean(v, axis), new_bufs)
            return loss, new_params, new_states, new_bufs

        P = PartitionSpec
        repl_spec = P()
        state_specs = {
            k: {n: (P(axis) if sh is self._row_sh else P())
                for n, sh in self._state_shardings[k].items()}
            for k in trainable}
        batch_specs = tuple(P(axis) if np.ndim(b) >= 1 else P()
                            for b in batch_vals)
        in_specs = (repl_spec, repl_spec, state_specs, repl_spec,
                    repl_spec) + batch_specs
        out_specs = (repl_spec, repl_spec, state_specs, repl_spec)
        fn = shard_map_compat(train_step, mesh, in_specs=in_specs,
                              out_specs=out_specs)

        def to_sh(spec_tree):
            return jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), spec_tree,
                is_leaf=lambda s: isinstance(s, PartitionSpec))

        state_sh = to_sh(state_specs)
        in_sh = (self._repl, self._repl, state_sh, self._repl,
                 self._repl) + tuple(to_sh(s) for s in batch_specs)
        out_sh = (self._repl, self._repl, state_sh, self._repl)
        self._step_fn = jax.jit(fn, donate_argnums=(0, 2),
                                in_shardings=in_sh, out_shardings=out_sh)

    def _build_sharded_2d(self, batch_vals):
        """The fsdp×tp traced body (round 21).  Params enter (and
        leave) in their composed STORAGE placement; per step each param
        is all-gathered over every axis its spec names (the ZeRO-3
        gather — under a 2D mesh the tp axis too acts as a storage
        axis for training, since compute here is batch-parallel over
        ALL chips), grads reduce-scatter straight back into the
        placement (one ``psum_scatter`` per sharded dim, a plain
        ``psum`` over the axes the spec does not name), and the
        elementwise update runs on the local shard with local state —
        no trailing param all-gather, the output IS the placement the
        serving steps consume.  Donation (params + opt states) and the
        compile-count contract are unchanged from the 1D path."""
        from ..core.jax_compat import shard_map_compat
        from jax.sharding import NamedSharding, PartitionSpec

        opt = self.optimizer
        trainable = self._trainable
        clip_norm = self.clip_norm
        mesh, axes = self._jmesh, self._axes
        sizes = dict(mesh.shape)
        live_axes = tuple(a for a in axes if sizes[a] > 1)
        total = self._deg
        mean_combine = self._shard_cfg.loss_reduction == "mean"
        specs = self._param_specs

        def linear_index():
            idx = jnp.asarray(0, jnp.int32)
            for a in axes:
                idx = idx * sizes[a] + jax.lax.axis_index(a)
            return idx

        def sync_grads(grads):
            """Every grad leaves reduced over ALL mesh axes and
            scattered into its param's placement: psum_scatter along
            each spec-named dim (major-to-minor within a dim), psum
            over the remaining axes."""
            out = {}
            for k in trainable:
                g = grads[k]
                remaining = [a for a in live_axes]
                for dim, entry in enumerate(specs[k]):
                    for name in _entry_names(entry):
                        g = jax.lax.psum_scatter(
                            g, name, scatter_dimension=dim, tiled=True)
                        remaining.remove(name)
                if remaining:
                    g = jax.lax.psum(g, tuple(remaining))
                if mean_combine:
                    g = g / total
                out[k] = g
            return out

        def train_step(params, frozen_vals, opt_states, lr, key, *batch):
            self.compile_count += 1
            # the ZeRO-3 compute gather: full value per spec-named axis
            full = {k: gather_spec_axes(params[k], specs[k])
                    for k in trainable}
            # distinct dropout stream per chip — the linear (…,fsdp,tp)
            # index matches the 1D dp path's replica order, so an
            # fsdp×tp run draws the same per-shard streams as dp at
            # equal total degree (the parity gate relies on it)
            loss_fn = self._make_loss_fn(
                frozen_vals, batch, jax.random.fold_in(key,
                                                       linear_index()))
            (loss, new_bufs), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(full)

            grads = sync_grads(grads)

            if clip_norm is not None:
                # global grad norm from the PLACED shards: per group of
                # params sharing a spec-axis set, sum local squares and
                # psum over exactly those axes (replicated contributions
                # count once; sharded ones sum to the full square norm)
                groups: Dict[tuple, Any] = {}
                for k in trainable:
                    ax = tuple(sorted(set(spec_axes(specs[k]))))
                    sq = jnp.sum(jnp.square(
                        grads[k].astype(jnp.float32)))
                    groups[ax] = groups.get(
                        ax, jnp.asarray(0.0, jnp.float32)) + sq
                tot = jnp.asarray(0.0, jnp.float32)
                for ax, sq in groups.items():
                    tot = tot + (jax.lax.psum(sq, ax) if ax else sq)
                gnorm = jnp.sqrt(tot)
                scale = clip_norm / jnp.maximum(gnorm, clip_norm)
                grads = {k: (g * scale).astype(g.dtype)
                         for k, g in grads.items()}

            hyper = {"lr": lr}
            new_params = {}
            new_states = {}
            for k in trainable:
                # params, grads and state are ALL in the placement —
                # the elementwise update needs no slicing and no
                # trailing gather (arXiv:2004.13336 generalized to 2D)
                np_, nst = opt._update_rule(params[k], grads[k],
                                            opt_states[k], hyper)
                new_params[k] = np_
                new_states[k] = nst
            loss = jax.lax.pmean(loss, live_axes) if mean_combine \
                else jax.lax.psum(loss, live_axes)
            new_bufs = jax.tree_util.tree_map(
                lambda v: jax.lax.pmean(v, live_axes), new_bufs)
            return loss, new_params, new_states, new_bufs

        P = PartitionSpec
        repl_spec = P()
        param_specs = {k: specs[k] for k in trainable}
        state_specs = {
            k: {n: sh.spec
                for n, sh in self._state_shardings[k].items()}
            for k in trainable}
        batch_specs = tuple(P(axes) if np.ndim(b) >= 1 else P()
                            for b in batch_vals)
        in_specs = (param_specs, repl_spec, state_specs, repl_spec,
                    repl_spec) + batch_specs
        out_specs = (repl_spec, param_specs, state_specs, repl_spec)
        fn = shard_map_compat(train_step, mesh, in_specs=in_specs,
                              out_specs=out_specs)

        def to_sh(spec_tree):
            return jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), spec_tree,
                is_leaf=lambda s: isinstance(s, PartitionSpec))

        in_sh = (to_sh(param_specs), self._repl, to_sh(state_specs),
                 self._repl, self._repl) + tuple(to_sh(s)
                                                 for s in batch_specs)
        out_sh = (self._repl, to_sh(param_specs), to_sh(state_specs),
                  self._repl)
        self._step_fn = jax.jit(fn, donate_argnums=(0, 2),
                                in_shardings=in_sh, out_shardings=out_sh)

    # -- checkpoint plumbing --------------------------------------------------
    # The CheckpointManager snapshots these LIVE (possibly ZeRO-sharded)
    # state arrays shard-wise at a step boundary; restore reshards them
    # onto whatever mesh/dp degree the resumed run is using.
    def opt_state_arrays(self) -> Dict[str, Any]:
        """Flat ``{"opt.<param>.<leaf>": array}`` of the live optimizer
        state — sharded leaves stay sharded (the manager saves each
        replica's shard with its global offset)."""
        out = {}
        for k in self._trainable:
            for name, v in self._opt_states[k].items():
                if hasattr(v, "shape"):
                    out[f"opt.{k}.{name}"] = v
        return out

    def load_opt_state_arrays(self, flat: Dict[str, Any]):
        """Restore state saved by :meth:`opt_state_arrays` — possibly
        under a DIFFERENT dp degree: each full (reassembled) array is
        ``device_put`` with THIS step's current sharding, which is the
        whole reshard path (array redistribution, arXiv:2112.01075).
        Unknown keys are ignored; missing keys keep their fresh init."""
        for k in self._trainable:
            st = self._opt_states[k]
            for name, cur in list(st.items()):
                full = flat.get(f"opt.{k}.{name}")
                if full is None or not hasattr(cur, "shape"):
                    continue
                val = jnp.asarray(np.asarray(full)).astype(cur.dtype)
                if tuple(val.shape) != tuple(cur.shape):
                    raise ValueError(
                        f"checkpointed state {k}.{name} has shape "
                        f"{val.shape}, current run expects {cur.shape}")
                if self._sharded:
                    sh = self._state_shardings[k].get(name)
                    if sh is not None:
                        val = jax.device_put(val, sh)
                # in-place: optimizer._state holds the same dict object
                st[name] = val

    @property
    def global_step(self) -> int:
        """Steps applied through this TrainStep (the optimizer's counter
        — restored by the checkpoint layer on resume)."""
        return int(self.optimizer._global_step)

    # -- common driver --------------------------------------------------------
    def _ensure_built(self, batch_vals):
        if self._step_fn is None:
            if self._sharded and getattr(self, "_mode", "1d") == "2d":
                self._build_sharded_2d(batch_vals)
            elif self._sharded:
                self._build_sharded(batch_vals)
            else:
                self._build()

    def _gather_inputs(self, batch):
        sd = self.model.state_dict()
        batch_vals = tuple(b._value if isinstance(b, Tensor)
                           else jnp.asarray(b) for b in batch)
        if self._sharded:
            for b in batch_vals:
                if np.ndim(b) >= 1 and b.shape[0] % self._deg:
                    # fail with an actionable message instead of the
                    # cryptic mid-jit divisibility error
                    raise ValueError(
                        f"sharded TrainStep: batch dim0={b.shape[0]} "
                        f"is not divisible by the mesh degree "
                        f"{self._deg}; use drop_last=True (Engine.fit "
                        f"does) or pad the tail batch")
            if getattr(self, "_mode", "1d") == "2d":
                self._place_params_2d(sd)
            else:
                self._place_replicated(sd)
            for k in self._trainable:
                self._refresh_state(k, sd[k])
        params = {k: sd[k]._value for k in self._trainable}
        frozen_vals = {k: sd[k]._value for k in self._frozen}
        return sd, params, frozen_vals, batch_vals

    def lower(self, *batch):
        """AOT-lower the fused step with the current params/shardings
        (used by DistModel.dist_main_program, the dist-attr read-back,
        and verify_sharded_update's HLO assertions)."""
        sd, params, frozen_vals, batch_vals = self._gather_inputs(batch)
        self._ensure_built(batch_vals)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        # fixed dummy key: lowering must not perturb the training RNG
        # stream (the key value cannot affect the lowered HLO)
        key = jax.random.PRNGKey(0)
        return self._step_fn.lower(params, frozen_vals, self._opt_states,
                                   lr, key, *batch_vals)

    def compiled_stats(self, *batch) -> Dict[str, Any]:
        """FLOPs + static memory sizes of the compiled fused step —
        the telemetry source for MFU (cost_analysis) and HBM headroom
        (memory_analysis).  AOT lower+compile of the SAME traced body
        (cached per instance: one extra compile, ever).  XLA reports
        PER-DEVICE numbers: under dp=8 sharding the flops are 1/8 of
        the global program — divide by per-chip peak for MFU, never by
        peak * device_count."""
        cached = getattr(self, "_compiled_stats", None)
        if cached is not None:
            return cached
        compiled = self.lower(*batch).compile()
        stats: Dict[str, Any] = {}
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            for src, dst in (("flops", "flops"),
                             ("bytes accessed", "bytes_accessed")):
                if ca.get(src):
                    stats[dst] = float(ca[src])
        except Exception:                             # noqa: BLE001
            pass
        try:
            ma = compiled.memory_analysis()
            for attr, dst in (
                    ("temp_size_in_bytes", "temp_bytes"),
                    ("argument_size_in_bytes", "argument_bytes"),
                    ("output_size_in_bytes", "output_bytes"),
                    ("generated_code_size_in_bytes", "code_bytes")):
                v = getattr(ma, attr, None)
                if v:
                    stats[dst] = int(v)
        except Exception:                             # noqa: BLE001
            pass
        self._compiled_stats = stats
        return stats

    def __call__(self, *batch):
        sd, params, frozen_vals, batch_vals = self._gather_inputs(batch)
        self._ensure_built(batch_vals)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        key = _random.next_key()
        loss, new_params, new_states, new_bufs = self._step_fn(
            params, frozen_vals, self._opt_states, lr, key, *batch_vals)
        for k, v in new_params.items():
            sd[k]._value = v
        # persist traced buffer updates (BatchNorm running stats)
        for k, v in new_bufs.items():
            sd[k]._value = v
        # update the per-param state DICTS in place: optimizer._state
        # holds the same dict objects, so optimizer.state_dict() stays
        # valid after the donated buffers die
        for k, nst in new_states.items():
            self._opt_states[k].update(nst)
        if getattr(self, "_mode", None) == "2d":
            # static per-dispatch param-gather payload (per chip)
            self._m_gather_bytes.inc(self._gather_bytes_per_step)
        if isinstance(self.optimizer._learning_rate, object) and \
                hasattr(self.optimizer._learning_rate, "step"):
            pass  # caller drives the scheduler
        self.optimizer._global_step += 1
        return Tensor._from_value(loss)
