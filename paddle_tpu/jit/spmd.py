"""Shared SPMD machinery for the fused compiled steps.

One home for everything both the training step (``jit/train_step.py``,
ZeRO-1/2 weight-update sharding) and the serving steps
(``jit/serving_step.py``, tensor-parallel multi-chip decode/prefill)
need to agree on: mesh/axis resolution, the :class:`ShardingConfig`
the callers hand in, the canonical per-weight-family
:class:`SpecLayout` (the ``PartitionSpec`` table tensor-parallel
serving shards the llama weight families by), and the small traced
helpers (vocab-parallel embedding, logits all-gather) the sharded
serving bodies compose under ``shard_map``.

Weight-family layout (Megatron-style tensor parallelism over a ``tp``
mesh axis; Linear weights are ``[in, out]``):

====================  =======================  =========================
family                spec                     collective it implies
====================  =======================  =========================
embed_tokens.weight   P(tp, None)  vocab-row   one psum after the masked
                                               local lookup (exact: every
                                               token's row lives on ONE
                                               chip, the others add 0)
q/k/v_proj.weight     P(None, tp)  head-col    none (activations stay
                                               replicated; outputs are
                                               this chip's head shard)
o_proj.weight         P(tp, None)  head-row    one psum per layer
gate/up_proj.weight   P(None, tp)  ffn-col     none
down_proj.weight      P(tp, None)  ffn-row     one psum per layer
lm_head.weight        P(None, tp)  vocab-col   one all-gather over the
                                               vocab shards (exact)
norms / biases(1-D    P() replicated           none
  except qkv bias)
KV page pools         P(None, None, tp, None)  none — each chip's paged
                                               attention sees only its
                                               kv-head shard of every
                                               page
====================  =======================  =========================

So one fused serving step pays: 1 embedding psum + 2 psums per
transformer layer (attention out, MLP out) + 1 logits all-gather —
"one collective per layer boundary", the pattern EQuARX
(arXiv:2506.17615) quantizes.  The psums split a contraction, so
activations agree with the single-chip step to float addition order
(ULPs); the embedding psum and logits all-gather are bit-exact.  The
parity contract is therefore on the sampled TOKENS, which the serving
benches gate byte-identically.

Stochastic sampling under tp (round 14) adds NO collective: the
``ops/sampling`` epilogue runs AFTER the exact logits all-gather, on
replicated logits with replicated knob/seed operands, and the
counter-based threefry draw is pure deterministic math — every chip
computes the identical token, byte-equal to the single-chip sampled
engine (gated in tests).  ``collective_bytes`` is therefore unchanged
by sampling.  Speculative verification stays single-chip for now (the
draft engine is unsharded); engines reject ``draft_model + mesh`` at
construction.

2D mesh (round 21) — fsdp×tp everywhere: the MaxText-style fsdp axis
of SNIPPETS.md [3] now composes with the tp table above instead of
collapsing away.  ``SpecLayout(fsdp_axis=...)`` shards each family's
NON-tp dimension over fsdp, so parameter *storage* is cut by
fsdp·tp per chip (ZeRO-3, the stage arXiv:2004.13336 stops short of)
while tp keeps sharding *compute*:

====================  =============  ==================================
family                1D tp spec     fsdp-composed spec
====================  =============  ==================================
embed_tokens.weight   P(tp, None)    P(tp, fsdp)        [V, h]
q/k/v_proj.weight     P(None, tp)    P(fsdp, tp)        [h, H*D]
o_proj.weight         P(tp, None)    P(tp, fsdp)        [H*D, h]
gate/up_proj.weight   P(None, tp)    P(fsdp, tp)        [h, I]
down_proj.weight      P(tp, None)    P(tp, fsdp)        [I, h]
lm_head.weight        P(None, tp)    P(fsdp, tp)        [h, V]
norms / unknown 1-D   P()            P(fsdp) when dim0 divides, else P()
KV page pools         P(,,tp,)       unchanged (replicated over fsdp)
====================  =============  ==================================

Serving gathers the fsdp shards back per dispatch (ONE tiled
all-gather per fsdp-sharded param, inside the shard_map body — the
payload ``spmd_allgather_bytes_total{site="serving_params"}``
accounts), then runs the unchanged Megatron-tp body; training keeps
params / grads / optimizer state in the fsdp×tp placement end to end
(gather for compute, reduce-scatter of grads back to the shard,
sharded update).  Because BOTH steps store the same placement, a
trained param tree serves with zero re-sharding: ``place_params`` is
buffer-identity on already-placed arrays.  Specs never name a replica
(dp) axis, so a 3D serving mesh ``(dp, fsdp, tp)`` replicates
weights/pools across dp for throughput with no code change.  Dims an
axis does not divide are PRUNED from the spec (storage optimization
degrades, never errors); ``mesh_2d`` builds the canonical mesh.

Expert parallelism (round 24) — the ``ep`` axis shards ONLY the
batched MoE expert banks' E dim (``w_gate/w_up/w_down [E, ., .]`` take
``P(ep, None, None)``, classified by :func:`mixtral_param_specs`); the
router, attention, norms and KV pools never name ``ep``, so they
replicate across it.  The fused MoE FFN inside the serving steps pays
two ``all_to_all`` exchanges (dispatch + combine, the reference's
global_scatter/global_gather pair) plus one token-stripe ``all_gather``
per MoE layer — accounted statically by
:meth:`TPContext.collective_bytes` under the ``ep_all_to_all`` /
``ep_all_gather`` keys.  Per-chip expert-weight HBM is exactly 1/ep.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["ShardingConfig", "SpecLayout", "TPContext",
           "resolve_mesh_axis", "llama_param_specs",
           "mixtral_param_specs", "validate_tp_serving",
           "validate_cp_serving", "validate_ep_serving",
           "tp_mesh", "mesh_2d", "cp_mesh", "ep_mesh",
           "tp_serving_context", "tp_embed", "tp_gather_logits",
           "tp_gather_logits_q8", "shard_arrays", "spec_axes",
           "prune_spec_axes", "gather_spec_axes", "fsdp_gather"]

P = PartitionSpec


class ShardingConfig:
    """Sharded-step config shared by :class:`~.train_step.TrainStep`
    (ZeRO weight-update sharding over a data-parallel axis) and the
    serving steps (tensor parallelism over a ``tp`` axis).

    stage: ZeRO stage for the TRAIN step — 1 (ZeRO-1 / 'os'): full-
        gradient all-reduce, optimizer state + weight update sharded
        over the dp axis; 2 (ZeRO-2 / 'os_g'): the grad sync itself
        becomes one reduce-scatter per coalesced bucket.  Serving
        ignores it.
    degree: number of shards; -1 infers the mesh axis size (a positive
        value must equal it — sub-axis sharding would need a mesh
        reshape).
    axis: mesh axis name to shard over ('dp' on the Engine mesh for
        training, 'tp' for tensor-parallel serving).
    bucket_mb: stage-2 coalesced reduce-scatter bucket size (train
        only).
    loss_reduction: how per-replica losses/grads combine (train only).
    """

    def __init__(self, stage: int = 1, degree: int = -1, axis: str = "dp",
                 bucket_mb: float = 25.0, loss_reduction: str = "mean"):
        if int(stage) not in (1, 2):
            raise ValueError(
                f"ShardingConfig stage must be 1 (os) or 2 (os_g), got "
                f"{stage!r}; stage-3 (params themselves sharded) is not a "
                f"stage knob here — pass a mesh with an 'fsdp' axis "
                f"(spmd.mesh_2d) and the fsdp×tp TrainStep stores the "
                f"params ZeRO-3-sharded as its natural layout")
        if loss_reduction not in ("mean", "sum"):
            raise ValueError(
                f"loss_reduction must be 'mean' or 'sum', got "
                f"{loss_reduction!r}")
        self.stage = int(stage)
        self.degree = int(degree)
        self.axis = axis
        self.bucket_mb = float(bucket_mb)
        self.loss_reduction = loss_reduction

    def __repr__(self):
        return (f"ShardingConfig(stage={self.stage}, degree={self.degree}, "
                f"axis={self.axis!r}, bucket_mb={self.bucket_mb}, "
                f"loss_reduction={self.loss_reduction!r})")


def resolve_mesh_axis(mesh, axis: str,
                      degree: int = -1,
                      candidates: Sequence[str] = ("dp", "sharding",
                                                   "data"),
                      ) -> Tuple[Mesh, str, int]:
    """Unwrap ``mesh`` to a jax Mesh and pick the axis to shard over.

    ``axis`` wins when present; otherwise the first name in
    ``candidates`` that exists on the mesh with size > 1.  ``degree``
    must equal the axis size or be -1 (infer).  Returns
    ``(jax_mesh, axis_name, axis_size)`` — size 1 means "degenerate:
    run the unsharded step".
    """
    from ..distributed.process_mesh import as_jax_mesh
    if mesh is None:
        raise ValueError("ShardingConfig requires a mesh")
    jmesh = as_jax_mesh(mesh)
    if axis not in jmesh.axis_names:
        axis = next((a for a in candidates
                     if a in jmesh.axis_names and jmesh.shape[a] > 1),
                    None)
        if axis is None:
            raise ValueError(
                f"no shardable axis on mesh {tuple(jmesh.axis_names)} "
                f"(wanted one of {tuple(candidates)})")
    deg = jmesh.shape[axis]
    if degree not in (-1, deg):
        raise ValueError(
            f"sharding degree {degree} must equal the '{axis}' axis "
            f"size {deg} (or -1 to infer)")
    return jmesh, axis, deg


def tp_mesh(tp: int, axis: str = "tp"):
    """A 1-D ``tp``-wide ProcessMesh over the first ``tp`` devices —
    the standard serving mesh (benches, tests, single-host engines).
    Reuse the train-step mesh instead when co-located (any mesh with a
    ``tp`` axis resolves)."""
    from ..distributed.process_mesh import ProcessMesh
    n = jax.device_count()
    if tp > n:
        raise ValueError(
            f"tp={tp} exceeds the {n} visible devices; for CPU dryruns "
            f"call paddle_tpu.testing.dryrun.force_cpu_devices first")
    return ProcessMesh(shape=[tp], dim_names=[axis])


def mesh_2d(fsdp: int, tp: int, replica: int = 1,
            fsdp_axis: str = "fsdp", tp_axis: str = "tp",
            replica_axis: str = "dp"):
    """The canonical 2D ``(fsdp, tp)`` ProcessMesh over the first
    ``replica*fsdp*tp`` devices — first-class instead of the ad-hoc
    device reshapes tests/benches used to hand-roll.  ``replica > 1``
    prepends a pure data-parallel axis (3D serving mesh: weights and
    KV pools replicate across it because specs never name it; the 2D
    train step treats it as extra batch parallelism)."""
    from ..distributed.process_mesh import ProcessMesh
    need = int(replica) * int(fsdp) * int(tp)
    n = jax.device_count()
    if need > n:
        raise ValueError(
            f"mesh_2d(replica={replica}, fsdp={fsdp}, tp={tp}) needs "
            f"{need} devices but only {n} are visible; for CPU dryruns "
            f"call paddle_tpu.testing.dryrun.force_cpu_devices first")
    if replica > 1:
        return ProcessMesh(shape=[replica, fsdp, tp],
                           dim_names=[replica_axis, fsdp_axis, tp_axis])
    return ProcessMesh(shape=[fsdp, tp], dim_names=[fsdp_axis, tp_axis])


def cp_mesh(cp: int, tp: int = 1, cp_axis: str = "cp",
            tp_axis: str = "tp"):
    """The serving ``(cp, tp)`` ProcessMesh over the first ``cp*tp``
    devices (round 22): ``cp`` stripes the KV pool's slot dimension so
    per-chip pool HBM is 1/cp, ``tp`` shards heads as before.
    ``tp=1`` gives the pure context-parallel mesh — weights replicate
    (no spec names ``cp``), only the pools stripe."""
    from ..distributed.process_mesh import ProcessMesh
    need = int(cp) * int(tp)
    n = jax.device_count()
    if need > n:
        raise ValueError(
            f"cp_mesh(cp={cp}, tp={tp}) needs {need} devices but only "
            f"{n} are visible; for CPU dryruns call "
            f"paddle_tpu.testing.dryrun.force_cpu_devices first")
    if tp > 1:
        return ProcessMesh(shape=[cp, tp], dim_names=[cp_axis, tp_axis])
    return ProcessMesh(shape=[cp], dim_names=[cp_axis])


def ep_mesh(ep: int, tp: int = 1, ep_axis: str = "ep",
            tp_axis: str = "tp"):
    """The serving ``(ep, tp)`` ProcessMesh over the first ``ep*tp``
    devices (round 24): ``ep`` shards the MoE expert banks' E dim so
    per-chip expert-weight HBM is 1/ep, ``tp`` shards heads/vocab as
    before.  ``tp=1`` gives the pure expert-parallel mesh — everything
    except the expert banks replicates (no other spec names ``ep``)."""
    from ..distributed.process_mesh import ProcessMesh
    need = int(ep) * int(tp)
    n = jax.device_count()
    if need > n:
        raise ValueError(
            f"ep_mesh(ep={ep}, tp={tp}) needs {need} devices but only "
            f"{n} are visible; for CPU dryruns call "
            f"paddle_tpu.testing.dryrun.force_cpu_devices first")
    if tp > 1:
        return ProcessMesh(shape=[ep, tp], dim_names=[ep_axis, tp_axis])
    return ProcessMesh(shape=[ep], dim_names=[ep_axis])


# ---------------------------------------------------------------------------
# canonical per-weight-family specs
# ---------------------------------------------------------------------------
class SpecLayout:
    """Canonical PartitionSpecs per llama weight family (see the module
    docstring's tables).  ``tp_axis`` shards compute (Megatron);
    ``fsdp_axis`` (round 21, MaxText-style) additionally shards each
    family's non-tp dimension for ZeRO-3 weight STORAGE.  Either axis
    may be ``None`` — a pure-fsdp layout (tp_axis=None) stores sharded
    weights but runs single-chip-math bodies after the gather."""

    def __init__(self, tp_axis: Optional[str] = "tp",
                 fsdp_axis: Optional[str] = None,
                 cp_axis: Optional[str] = None,
                 ep_axis: Optional[str] = None):
        self.tp_axis = tp_axis
        self.fsdp_axis = fsdp_axis
        # round 22: context-parallel axis — stripes ONLY the KV pool's
        # slot dim (weights never name it, so they replicate across cp)
        self.cp_axis = cp_axis
        # round 24: expert-parallel axis — shards ONLY the batched MoE
        # expert banks' E dim (router/attention/pools never name it)
        self.ep_axis = ep_axis

    def embeddings(self) -> PartitionSpec:
        """[V, h] vocab-row sharded: masked local lookup + one exact
        psum (Megatron vocab-parallel embedding); fsdp on the hidden
        dim."""
        return P(self.tp_axis, self.fsdp_axis)

    def qkv_projection(self) -> PartitionSpec:
        """[h, H*D] column (head) sharded: each chip projects only its
        own query/kv heads; fsdp on the input dim."""
        return P(self.fsdp_axis, self.tp_axis)

    def qkv_bias(self) -> PartitionSpec:
        """[H*D] follows its projection's column shard (the one dim is
        tp's, so no fsdp composition)."""
        return P(self.tp_axis)

    def attn_output(self) -> PartitionSpec:
        """[H*D, h] row sharded — the per-layer psum boundary; fsdp on
        the output dim."""
        return P(self.tp_axis, self.fsdp_axis)

    def ffn_up(self) -> PartitionSpec:
        """gate/up [h, I] column sharded (SwiGLU is elementwise on the
        shard); fsdp on the input dim."""
        return P(self.fsdp_axis, self.tp_axis)

    def ffn_down(self) -> PartitionSpec:
        """down [I, h] row sharded — the other per-layer psum; fsdp on
        the output dim."""
        return P(self.tp_axis, self.fsdp_axis)

    def lm_head(self) -> PartitionSpec:
        """[h, V] vocab-column sharded: local [*, V/tp] logits, one
        exact all-gather before the on-device argmax; fsdp on the
        input dim."""
        return P(self.fsdp_axis, self.tp_axis)

    def replicated(self) -> PartitionSpec:
        return P()

    def fsdp_default(self) -> PartitionSpec:
        """Unknown / 1-D families (norm weights, generic Linear params)
        under an fsdp axis: shard dim0 for the storage win — pruned
        back to replicated when dim0 does not divide (see
        :func:`prune_spec_axes`)."""
        return P(self.fsdp_axis) if self.fsdp_axis else P()

    def kv_pool(self) -> PartitionSpec:
        """[phys_pages, block_size, Hkv, D] sharded over kv heads (tp)
        and — round 22 — striped over the block_size SLOT dim (cp):
        each chip holds slots ``[r*bs/cp, (r+1)*bs/cp)`` of EVERY page
        for its head shard, so per-chip pool HBM is exactly
        1/(cp*tp).  Slot striping keeps the page table, refcounts, COW
        and prefix keys chip-local and identical on every chip."""
        return P(None, self.cp_axis, self.tp_axis, None)

    def kv_scale(self) -> PartitionSpec:
        """An int8 pool's [phys_pages, Hkv] absmax tables follow the
        pool's kv-head shard (quantize/dequantize/rescale are all
        head-local math)."""
        return P(None, self.tp_axis)

    def expert_bank(self) -> PartitionSpec:
        """Batched MoE expert weights ``[E, in, out]``
        (w_gate/w_up/w_down): the E dim shards over ep, so each chip
        stores and runs only its own experts; the in/out dims stay
        whole (the grouped einsums are per-expert dense matmuls)."""
        return P(self.ep_axis, None, None)

    def expert_bank_scale(self) -> PartitionSpec:
        """An int8 expert bank's ``[E, 1, out]`` per-expert-per-channel
        absmax tables follow the bank's E shard (dequant is
        expert-local math)."""
        return P(self.ep_axis, None, None)

    def col_weight_scale(self) -> PartitionSpec:
        """Per-output-channel scale vector of a COLUMN-sharded weight
        (qkv / gate / up / lm_head): the channel axis IS the sharded
        output axis, so the scales shard with it."""
        return P(self.tp_axis)

    def row_weight_scale(self) -> PartitionSpec:
        """Per-output-channel scale vector of a ROW-sharded weight
        (o_proj / down): the output axis is the replicated hidden dim,
        so every chip holds the full vector."""
        return P()


def llama_param_specs(keys: Iterable[str],
                      layout: Optional[SpecLayout] = None,
                      shapes: Optional[Dict[str, Tuple[int, ...]]] = None,
                      mesh: Optional[Mesh] = None,
                      ) -> Dict[str, PartitionSpec]:
    """Classify llama state-dict keys into the canonical family specs.

    Unknown families (norm weights, scalars) stay replicated under a
    pure-tp layout — correct for anything whose math runs identically
    on every chip; under an fsdp layout they take ``fsdp_default()``
    (dim0 storage shard) instead.

    ``shapes`` + ``mesh`` (required whenever ``layout.fsdp_axis`` is
    set) prune every spec against the actual dims: an axis that does
    not divide a dim is dropped from that dim's entry
    (:func:`prune_spec_axes`) — fsdp is a storage optimization that
    degrades instead of erroring, and BOTH the train step and the
    serving context run the same pruning so the placements agree
    (the zero-re-sharding contract).

    Serving-PTQ trees (``quantization.functional.quantize_param_tree``)
    interleave per-channel scale vectors under ``<param>::scale`` keys;
    those classify by their BASE weight's family — sharded with the
    output axis for column-sharded weights (qkv / gate / up / lm_head),
    replicated for row-sharded ones (o_proj / down) whose output axis
    is the hidden dim.  int8 weights themselves keep their family's
    2-D spec (quantization changes the dtype, not the layout).
    """
    from ..quantization.functional import WEIGHT_SCALE_SUFFIX
    layout = layout or SpecLayout()
    specs: Dict[str, PartitionSpec] = {}
    for k in keys:
        if k.endswith(WEIGHT_SCALE_SUFFIX):
            base = k[:-len(WEIGHT_SCALE_SUFFIX)]
            if any(p in base for p in ("q_proj", "k_proj", "v_proj",
                                       "gate_proj", "up_proj",
                                       "lm_head")):
                specs[k] = layout.col_weight_scale()
            elif "o_proj" in base or "down_proj" in base:
                specs[k] = layout.row_weight_scale()
            else:
                specs[k] = layout.replicated()
        elif "embed_tokens" in k:
            specs[k] = layout.embeddings()
        elif any(p in k for p in ("q_proj", "k_proj", "v_proj")):
            specs[k] = layout.qkv_bias() if k.endswith("bias") \
                else layout.qkv_projection()
        elif "o_proj" in k:
            specs[k] = layout.attn_output()
        elif "gate_proj" in k or "up_proj" in k:
            specs[k] = layout.ffn_up()
        elif "down_proj" in k:
            specs[k] = layout.ffn_down()
        elif "lm_head" in k:
            specs[k] = layout.lm_head()
        else:
            specs[k] = layout.fsdp_default()
    if shapes is not None and mesh is not None:
        specs = {k: prune_spec_axes(s, shapes[k], mesh)
                 if k in shapes else s for k, s in specs.items()}
    return specs


_EXPERT_BANK_FAMILIES = ("w_gate", "w_up", "w_down")


def mixtral_param_specs(keys: Iterable[str],
                        layout: Optional[SpecLayout] = None,
                        shapes: Optional[Dict[str, Tuple[int, ...]]]
                        = None,
                        mesh: Optional[Mesh] = None,
                        ) -> Dict[str, PartitionSpec]:
    """The MoE name classifier (round 24): batched expert banks
    (``...block_sparse_moe.w_gate/w_up/w_down``, plus their PTQ
    ``::scale`` tables) take :meth:`SpecLayout.expert_bank` —
    ``P(ep, None, None)`` — the router (``...block_sparse_moe.gate.*``)
    replicates (its logits drive a top-k whose ties must agree on every
    chip), and every other key delegates to :func:`llama_param_specs`
    (Mixtral's attention/embedding/lm_head ARE the llama families).
    Pruning semantics match llama_param_specs exactly."""
    from ..quantization.functional import WEIGHT_SCALE_SUFFIX
    layout = layout or SpecLayout()
    keys = list(keys)
    specs: Dict[str, PartitionSpec] = {}
    rest = []
    for k in keys:
        base = k[:-len(WEIGHT_SCALE_SUFFIX)] \
            if k.endswith(WEIGHT_SCALE_SUFFIX) else k
        if any(base.endswith(f) for f in _EXPERT_BANK_FAMILIES):
            specs[k] = layout.expert_bank_scale() if base != k \
                else layout.expert_bank()
        elif "block_sparse_moe.gate." in base:
            specs[k] = layout.replicated()
        else:
            rest.append(k)
    specs.update(llama_param_specs(rest, layout))
    if shapes is not None and mesh is not None:
        specs = {k: prune_spec_axes(s, shapes[k], mesh)
                 if k in shapes else s for k, s in specs.items()}
    return specs


# ---------------------------------------------------------------------------
# spec algebra (shared by the 2D train step and the serving prologue)
# ---------------------------------------------------------------------------
def _entry_names(entry) -> Tuple[str, ...]:
    """A PartitionSpec entry's axis names: None -> (), 'x' -> ('x',),
    ('x', 'y') -> ('x', 'y')."""
    if entry is None:
        return ()
    if isinstance(entry, tuple):
        return tuple(entry)
    return (entry,)


def spec_axes(spec: PartitionSpec) -> Tuple[str, ...]:
    """Every mesh axis a spec names, in dim order."""
    out = []
    for entry in spec:
        out.extend(_entry_names(entry))
    return tuple(out)


def prune_spec_axes(spec: PartitionSpec, shape: Tuple[int, ...],
                    mesh: Mesh) -> PartitionSpec:
    """Drop axis names a dim cannot honor: any name whose (cumulative)
    degree does not divide the dim size, and any spec entry past the
    array's rank.  The survivors are exactly the shardings
    ``NamedSharding(mesh, spec)`` can place, so train and serve agree
    on the SAME pruned placement by construction."""
    entries = []
    for dim, entry in enumerate(spec):
        if dim >= len(shape):
            break
        keep, part = [], 1
        for name in _entry_names(entry):
            size = mesh.shape.get(name, 1) if hasattr(mesh.shape, "get") \
                else dict(mesh.shape).get(name, 1)
            if size > 1 and shape[dim] % (part * size) == 0:
                keep.append(name)
                part *= size
        entries.append(tuple(keep) if len(keep) > 1
                       else (keep[0] if keep else None))
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def gather_spec_axes(x, spec: PartitionSpec,
                     axes: Optional[Sequence[str]] = None):
    """Inside a shard_map body: all-gather ``x`` (tiled, in axis-major
    order) along every dim whose spec entry names one of ``axes``
    (None = every named axis), reconstructing the full value from the
    placed shard.  The inverse of the per-dim sharding the spec
    declares — ONE tiled all-gather per (dim, axis) pair.  A tuple
    entry splits its dim major-to-minor, so the gather runs minor
    first (reversed) to land every block at its global offset."""
    for dim, entry in enumerate(spec):
        for name in reversed(_entry_names(entry)):
            if axes is None or name in axes:
                x = jax.lax.all_gather(x, name, axis=dim, tiled=True)
    return x


def fsdp_gather(x, spec: PartitionSpec, fsdp_axis: str):
    """The serving prologue's param gather: undo only the fsdp STORAGE
    shard, leaving the tp compute shard in place."""
    return gather_spec_axes(x, spec, (fsdp_axis,))


def shard_arrays(arrays: Dict[str, jnp.ndarray], mesh: Mesh,
                 specs: Dict[str, PartitionSpec]) -> Dict[str, jnp.ndarray]:
    """device_put each array with its spec's NamedSharding (the one-time
    placement at sharded-step init; params never cross the link again)."""
    return {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in arrays.items()}


def validate_tp_serving(cfg, degree: int, pool_kv_heads: Optional[int]
                        = None) -> None:
    """Every divisibility constraint tensor-parallel serving needs,
    checked at ENGINE CONSTRUCTION with one actionable message —
    instead of a shard_map shape failure deep inside tracing."""
    if degree <= 1:
        return
    problems = []
    for name, val in (("num_attention_heads", cfg.num_attention_heads),
                      ("num_key_value_heads", cfg.num_key_value_heads),
                      ("vocab_size", cfg.vocab_size),
                      ("intermediate_size", cfg.intermediate_size)):
        if val % degree:
            problems.append(f"{name}={val}")
    if pool_kv_heads is not None \
            and pool_kv_heads != cfg.num_key_value_heads:
        problems.append(
            f"KV page pool has {pool_kv_heads} kv heads but the model "
            f"config says {cfg.num_key_value_heads}")
    if problems:
        raise ValueError(
            "tensor-parallel serving with tp=%d requires every sharded "
            "dimension to divide by tp; violated: %s.  Pick a tp that "
            "divides the head/vocab/ffn dims (or pad the model)."
            % (degree, ", ".join(problems)))


def validate_cp_serving(cp_degree: int, block_size: int,
                        quantized_kv: bool = False,
                        spec_decode: bool = False) -> None:
    """Every constraint context-parallel serving needs, checked at
    ENGINE CONSTRUCTION with one actionable message (round 22,
    mirroring :func:`validate_tp_serving`).  cp stripes the pool's
    SLOT dim, so the page ``block_size`` must divide by cp; int8 KV
    and speculative decoding are rejected (their pool/scatter layouts
    assume one chip holds a page's full slot range)."""
    if cp_degree <= 1:
        return
    if block_size % cp_degree:
        raise ValueError(
            f"context-parallel serving with cp={cp_degree} requires the "
            f"KV page block_size to divide by cp (each chip owns "
            f"block_size/cp slots of every page); got "
            f"block_size={block_size}.  Pick a block_size that divides "
            f"by cp, or lower cp.")
    if quantized_kv:
        raise ValueError(
            f"context-parallel serving (cp={cp_degree}) does not "
            f"support the int8 KV pool: the [phys_pages, Hkv] absmax "
            f"tables are page-global and would diverge across slot "
            f"stripes.  Serve with kv_dtype=None (fp32 pool) under cp.")
    if spec_decode:
        raise ValueError(
            f"context-parallel serving (cp={cp_degree}) does not "
            f"support speculative decoding yet: the draft/verify steps "
            f"bypass the striped scatter.  Disable spec-decode under "
            f"cp.")


def validate_ep_serving(num_experts: int, ep_degree: int,
                        spec_decode: bool = False,
                        budgets: Sequence[int] = ()) -> None:
    """Every constraint expert-parallel serving needs, checked at
    ENGINE CONSTRUCTION with one actionable message (round 24,
    mirroring :func:`validate_cp_serving`).  ep shards the expert
    banks' E dim and stripes the fused dispatch over token budgets, so
    both E and every compiled budget must divide by ep; speculative
    decoding is rejected."""
    if ep_degree <= 1:
        return
    if not num_experts:
        raise ValueError(
            f"expert-parallel serving with ep={ep_degree} needs an MoE "
            f"model (num_local_experts on the config): a dense model "
            f"has no expert banks for the ep axis to shard — drop the "
            f"ep mesh axis or serve the Mixtral-family model.")
    if num_experts % ep_degree:
        raise ValueError(
            f"expert-parallel serving with ep={ep_degree} requires the "
            f"expert count to divide by ep (each chip owns E/ep "
            f"experts); got num_local_experts={num_experts}.  Pick an "
            f"ep that divides E, or lower ep.")
    if spec_decode:
        raise ValueError(
            f"expert-parallel serving (ep={ep_degree}) does not "
            f"support speculative decoding yet: the draft/verify steps "
            f"bypass the fused MoE dispatch.  Disable spec-decode "
            f"under ep.")
    bad = [b for b in budgets if int(b) % ep_degree]
    if bad:
        raise ValueError(
            f"expert-parallel serving (ep={ep_degree}) stripes each "
            f"compiled token budget over the ep axis, so every budget "
            f"must divide by ep; violated: {bad}.  Adjust the mixed "
            f"budget set (token_budgets) or lower ep.")


class TPContext:
    """Resolved tensor-parallel serving context, shared by every
    serving step of one engine: the jax mesh, the axis name/degree, the
    spec layout, the per-param specs, and the ONE placed copy of the
    sharded parameters (placed lazily on first use; params are
    read-only in serving, so they never cross the host link again)."""

    def __init__(self, mesh: Mesh, axis: Optional[str], degree: int,
                 layout: SpecLayout, specs: Dict[str, PartitionSpec],
                 fsdp_axis: Optional[str] = None, fsdp_degree: int = 1,
                 cp_axis: Optional[str] = None, cp_degree: int = 1,
                 ep_axis: Optional[str] = None, ep_degree: int = 1):
        self.mesh = mesh
        self.axis = axis                  # tp axis (None: pure fsdp)
        self.degree = degree              # tp degree (compute shard)
        self.fsdp_axis = fsdp_axis if fsdp_degree > 1 else None
        self.fsdp_degree = fsdp_degree if fsdp_degree > 1 else 1
        self.cp_axis = cp_axis if cp_degree > 1 else None
        self.cp_degree = cp_degree if cp_degree > 1 else 1
        self.ep_axis = ep_axis if ep_degree > 1 else None
        self.ep_degree = ep_degree if ep_degree > 1 else 1
        self.layout = layout
        self.specs = specs
        self._placed: Optional[Dict[str, jnp.ndarray]] = None
        self._placed_src: Dict[str, jnp.ndarray] = {}
        self._fsdp_bytes: Optional[int] = None

    def _place_one(self, k, v):
        """device_put UNLESS the array already carries exactly this
        sharding — then keep the buffer itself.  This is the
        train-to-serve zero-re-sharding contract: the 2D TrainStep's
        outputs are placed with the SAME mesh/specs, so serving them
        is pointer identity, not a host (or even device) copy."""
        sh = NamedSharding(self.mesh, self.specs[k])
        if isinstance(v, jax.Array) and getattr(v, "sharding", None) == sh:
            return v
        return jax.device_put(v, sh)

    def place_params(self, arrays: Dict[str, jnp.ndarray]
                     ) -> Dict[str, jnp.ndarray]:
        """Sharded placement with staleness tracking: jax arrays are
        immutable, so a weight update (checkpoint load, requantize)
        rebinds the source array — detected per key by identity against
        a HELD reference (a bare id() could be fooled by address reuse
        after the old array is freed) and only the changed params are
        re-placed.  Steady-state serving pays an `is` comparison per
        param, never a transfer; an array that ALREADY carries its
        target sharding (the 2D train step's placed output) is adopted
        by identity, never copied."""
        if self._placed is None:
            self._placed = {k: self._place_one(k, v)
                            for k, v in arrays.items()}
            self._placed_src = dict(arrays)
            return self._placed
        for k, v in arrays.items():
            if self._placed_src.get(k) is not v:
                self._placed[k] = self._place_one(k, v)
                self._placed_src[k] = v
        return self._placed

    def fsdp_gather_bytes(self, arrays: Dict[str, jnp.ndarray]) -> int:
        """Per-chip bytes RECEIVED by the serving prologue's fsdp param
        all-gathers in one sharded dispatch (0 without an fsdp axis):
        for each fsdp-sharded param, the chip holds 1/(tp_part*fsdp)
        and receives the other (fsdp-1) fsdp shards of its tp slice.
        Static per engine — cached on first call (the accounting behind
        ``spmd_allgather_bytes_total{site=...}``)."""
        if self.fsdp_axis is None:
            return 0
        if self._fsdp_bytes is not None:
            return self._fsdp_bytes
        sizes = dict(self.mesh.shape)
        total = 0
        for k, v in arrays.items():
            spec = self.specs.get(k)
            if spec is None:
                continue
            names = spec_axes(spec)
            if self.fsdp_axis not in names:
                continue
            part = 1
            for n in names:
                part *= sizes.get(n, 1)
            fdeg = sizes.get(self.fsdp_axis, 1)
            nbytes = int(np.prod(v.shape)) * v.dtype.itemsize \
                if v.shape else v.dtype.itemsize
            total += nbytes // part * (fdeg - 1)
        self._fsdp_bytes = total
        return total

    def collective_bytes(self, cfg, n_tokens: int,
                         n_gather_rows: int,
                         quant_gather: bool = False) -> Dict[str, int]:
        """Per-chip collective payload of ONE sharded serving dispatch:
        (1 + 2L) psums of [n_tokens, hidden] (embedding + the two
        per-layer boundaries) and one all-gather of the
        [n_gather_rows, vocab/tp] logits shard — the static-per-shape
        accounting behind ``serving_tp_collective_bytes_total``.

        ``quant_gather=True`` accounts the EQuARX-style int8 logits
        all-gather (``tp_gather_logits_q8``): one byte per logit plus
        the 4-byte per-shard scale — the payload the quantized
        collective actually moves (reported under
        ``serving_quant_collective_bytes_total`` too).

        With a cp axis (round 22) the attention stripe merge adds one
        ``all_gather`` of the ``(o, m, l)`` fp32 partial rows per layer
        — per chip ``L · n_tokens · H_local · (D + 2) · 4`` payload
        bytes received from each of the other ``cp - 1`` members —
        reported under the separate ``"cp_merge"`` key (routed to
        ``serving_cp_collective_bytes_total{op="all_gather"}``)."""
        if self.degree <= 1:
            # pure-fsdp / pure-cp serving: the body runs single-chip
            # math (no tp activation collectives)
            out = {"psum": 0, "all_gather": 0}
        else:
            item = 2 if cfg.dtype == "bfloat16" else 4
            shard = n_gather_rows * (cfg.vocab_size // self.degree)
            out = {
                "psum": (2 * cfg.num_hidden_layers + 1) * n_tokens
                * cfg.hidden_size * item,
                "all_gather": shard + 4 if quant_gather else shard * item,
            }
        if self.cp_degree > 1:
            h_local = cfg.num_attention_heads // self.degree
            d = cfg.hidden_size // cfg.num_attention_heads
            out["cp_merge"] = (cfg.num_hidden_layers * n_tokens
                               * h_local * (d + 2) * 4
                               * (self.cp_degree - 1))
        if self.ep_degree > 1:
            # round 24 MoE dispatch (per MoE layer): two all_to_all
            # exchanges of the [E, Tl*k, D] send/return buffers — the
            # chip keeps its own 1/ep slice, so (ep-1)/ep of each
            # buffer crosses the link — plus one all_gather where the
            # chip receives the other (ep-1) token stripes [Tl, D]
            item = 2 if cfg.dtype == "bfloat16" else 4
            ep = self.ep_degree
            E = cfg.num_local_experts
            k = cfg.num_experts_per_tok
            L = cfg.num_hidden_layers
            tl = n_tokens // ep
            buf = E * (tl * k) * cfg.hidden_size * item
            out["ep_all_to_all"] = 2 * L * buf * (ep - 1) // ep
            out["ep_all_gather"] = (L * (ep - 1) * tl
                                    * cfg.hidden_size * item)
        return out

    def pool_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.layout.kv_pool())

    def kv_scale_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.layout.kv_scale())

    def named(self, spec_tree):
        """PartitionSpec tree -> NamedSharding tree on this mesh (jit
        in_shardings/out_shardings from shard_map in_specs/out_specs)."""
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), spec_tree,
            is_leaf=lambda s: isinstance(s, PartitionSpec))

    def __repr__(self):
        return (f"TPContext(axis={self.axis!r}, degree={self.degree}, "
                f"fsdp_axis={self.fsdp_axis!r}, "
                f"fsdp_degree={self.fsdp_degree}, "
                f"cp_axis={self.cp_axis!r}, cp_degree={self.cp_degree}, "
                f"ep_axis={self.ep_axis!r}, ep_degree={self.ep_degree}, "
                f"mesh={tuple(self.mesh.shape.items())})")


def tp_serving_context(model, mesh, sharding: Optional[ShardingConfig]
                       = None) -> Optional[TPContext]:
    """Resolve engine-construction arguments into a :class:`TPContext`
    (or None when every sharding axis degenerates to 1 — run the
    single-chip step).  Validates every tp divisibility constraint up
    front; an ``fsdp`` mesh axis (round 21) composes weight-storage
    sharding on top (specs pruned per param shape), and any OTHER mesh
    axis — e.g. a ``dp`` replica axis — is simply never named by a
    spec, so weights and pools replicate across it."""
    cfg = sharding or ShardingConfig(axis="tp")
    from ..distributed.process_mesh import as_jax_mesh
    jmesh = as_jax_mesh(mesh) if mesh is not None else None
    fsdp_axis = "fsdp" if jmesh is not None \
        and "fsdp" in jmesh.axis_names else None
    fsdp_deg = jmesh.shape["fsdp"] if fsdp_axis else 1
    cp_axis = "cp" if jmesh is not None \
        and "cp" in jmesh.axis_names else None
    cp_deg = jmesh.shape["cp"] if cp_axis else 1
    ep_axis = "ep" if jmesh is not None \
        and "ep" in jmesh.axis_names else None
    ep_deg = jmesh.shape["ep"] if ep_axis else 1
    try:
        jmesh, axis, deg = resolve_mesh_axis(
            mesh, cfg.axis, cfg.degree, candidates=("tp", "model", "mp"))
    except ValueError:
        # no tp axis at all — a pure-fsdp (or fsdp×dp) mesh is still a
        # sharded-storage serving context, a pure-cp mesh (round 22) a
        # pool-striped one, and a pure-ep mesh (round 24) an
        # expert-sharded one (size-1 axes degenerate below); anything
        # else re-raises
        if fsdp_axis is None and cp_axis is None and ep_axis is None:
            raise
        axis, deg = None, 1
    if deg <= 1 and fsdp_deg <= 1 and cp_deg <= 1 and ep_deg <= 1:
        return None
    if deg > 1:
        validate_tp_serving(model.config, deg)
    layout = SpecLayout(tp_axis=axis if deg > 1 else None,
                        fsdp_axis=fsdp_axis if fsdp_deg > 1 else None,
                        cp_axis=cp_axis if cp_deg > 1 else None,
                        ep_axis=ep_axis if ep_deg > 1 else None)
    sd = model.state_dict()
    shapes = {k: tuple(t._value.shape) for k, t in sd.items()}
    specs_fn = mixtral_param_specs \
        if getattr(model.config, "num_local_experts", 0) \
        else llama_param_specs
    specs = specs_fn(sd.keys(), layout, shapes=shapes, mesh=jmesh)
    return TPContext(jmesh, axis if deg > 1 else None, deg, layout,
                     specs, fsdp_axis=fsdp_axis, fsdp_degree=fsdp_deg,
                     cp_axis=cp_axis if cp_deg > 1 else None,
                     cp_degree=cp_deg,
                     ep_axis=ep_axis if ep_deg > 1 else None,
                     ep_degree=ep_deg)


# ---------------------------------------------------------------------------
# traced helpers (composed inside the shard_map'd serving bodies)
# ---------------------------------------------------------------------------
def tp_embed(table_local, tokens, axis: str):
    """Vocab-parallel embedding lookup (Megatron): ``table_local`` is
    this chip's [V/tp, h] row shard; returns the REPLICATED [..., h]
    embeddings.  Exact: each token's row lives on exactly one chip, so
    the psum adds zeros from every other chip — bit-identical to the
    single-chip gather."""
    vs = table_local.shape[0]
    start = jax.lax.axis_index(axis).astype(jnp.int32) * vs
    local = tokens.astype(jnp.int32) - start
    ok = (local >= 0) & (local < vs)
    e = table_local[jnp.clip(local, 0, vs - 1)]
    e = jnp.where(ok[..., None], e, jnp.zeros((), e.dtype))
    return jax.lax.psum(e, axis)


def tp_gather_logits(logits_local, axis: str):
    """All-gather the [*, V/tp] vocab-sharded logits into the
    replicated [*, V] block (exact — pure concatenation in chip order,
    which IS vocab order under the column shard), so the on-device
    argmax sees the same values as the single-chip step."""
    return jax.lax.all_gather(logits_local, axis,
                              axis=logits_local.ndim - 1, tiled=True)


def tp_gather_logits_q8(logits_local, axis: str):
    """EQuARX-style (arXiv:2506.17615) quantized logits all-gather:
    each chip quantizes its [*, V/tp] vocab shard to symmetric int8
    with ONE per-shard absmax scale, the gather moves int8 codes (+ a
    4-byte scale each) instead of fp words — ~4× (fp32) / ~2× (bf16)
    less interconnect payload — and every chip dequantizes each shard
    with its own gathered scale before the argmax.

    NOT exact: two logits within ``absmax/127`` of each other can swap
    order after the round trip, so engines enable this behind a
    measured token-match-rate gate (a tolerance gate, not byte parity
    — the serving quantization bench reports the rate per workload).
    """
    from ..quantization.functional import (dequantize_symmetric,
                                           quantize_symmetric)
    x = logits_local.astype(jnp.float32)
    s = jnp.max(jnp.abs(x))                              # per-shard
    q = quantize_symmetric(x, s).astype(jnp.int8)
    gq = jax.lax.all_gather(q, axis, axis=q.ndim - 1, tiled=True)
    gs = jax.lax.all_gather(s, axis)                     # [tp]
    tp = gs.shape[0]
    lead, V = gq.shape[:-1], gq.shape[-1]
    out = dequantize_symmetric(gq.reshape(lead + (tp, V // tp)),
                               gs[:, None])
    return out.reshape(lead + (V,)).astype(logits_local.dtype)
