"""The fully-fused compiled serving step, and the page-moving helpers.

The serving analog of ``TrainStep``: one engine step — every
transformer layer (projections, fused RoPE, paged KV-cache write,
ragged paged attention, MLP or MoE), the final norm, the LM head and
the sampler — traced into ONE XLA module per total-token budget
(:class:`MixedStep`), with the per-layer KV-cache pages passed as
donated arguments so the write is an in-place HBM update.  Parity
intent: the reference's ``AnalysisPredictor::ZeroCopyRun`` single-graph
serving execution (analysis_predictor.h:210) driven per token by the
block_multihead_attention kernel.

Shape policy: the only traced shape that varies is the token budget.
Every span descriptor is traced data, padding tokens write to the
cache's sink page (PagedKVCache ``sink_block``) and padding spans' rows
are ignored by the host, so admission, eviction and slot churn never
change a traced shape: compiles are bounded by the budget-set size
(``compile_counts`` asserts this in tests).

The only per-step host traffic is one packed int32 operand in and the
[max_spans] int32 next-token fetch out — sampling runs on device, so
the logits never cross the link.

Tensor parallelism (multi-chip serving): the step accepts
``mesh + ShardingConfig(axis='tp')`` (or a prebuilt
:class:`~.spmd.TPContext`, which the engine shares with its draft step
so parameters are placed once).  The SAME traced body then runs as an
explicit SPMD program (``shard_map`` over the tp axis): weights shard
by the canonical per-family specs in ``jit/spmd.py`` (vocab-row
embeddings, head-column QKV, head-row attention-out, ffn-column
gate/up, ffn-row down, vocab-column LM head), the paged KV pools shard
over kv heads (each chip's paged-attention launch sees only its head
shard of every page), and activations cross chip boundaries through
exactly one psum per layer boundary (attention out, MLP out) plus one
exact embedding psum and one exact logits all-gather.  Donation, the
compile-count invariant and the single packed int32 host transfer all
survive sharding unchanged.

Quantization: when the engine's pools are int8
(``PagedKVCache(kv_dtype="int8")``) the traced body switches to the
quantize-on-write/dequant-on-read ops and threads the per-layer scale
tables through as extra donated operands (EMPTY tuples on the fp path,
so the fp trace holds none of it); a serving-PTQ weight tree (int8 +
``::scale`` vectors) replaces the fp params operand and
``_materialize_params`` dequantizes it inside the trace;
``quant_collectives`` swaps the exact tp logits all-gather for the
EQuARX-style int8 one.  All tolerance-gated.

Sampling + speculative decoding: ``sampling=True`` swaps the greedy
argmax for the ``ops/sampling`` epilogue — per-request temperature /
top-k / top-p with a per-slot seeded counter-based PRNG (``fold_in`` on
the request seed + the sampled token's global position).  Every knob
and seed is traced DATA: the packed buffer's span rows grow by four
columns (fp knobs BITCAST into the int32 lane) — so changing a
temperature or a seed never retraces, and ``temperature=0`` rows take
the exact greedy argmax.  Under tp the epilogue runs AFTER the exact
logits all-gather on replicated data, so tp sampling is byte-identical
to single-chip.  ``spec_k=K`` puts the speculative VERIFY epilogue into
the step: spans may carry up to K draft tokens (an ``n_draft`` pack
column), the LM head sees each span's K+2 gathered rows instead of 1,
and the standard accept/reject + rejection-resampling scan
(``ops/sampling.spec_verify``) emits ``(token, n_acc)`` per span.
``return_probs=True`` (the draft model's role) additionally returns
each span's filtered proposal distribution, device-resident, for the
verifier's residual.

The per-layer pre-attention transforms run through the fused RoPE+QKV
epilogue (``ops/pallas_kernels.rope_qkv_epilogue`` — rope(q), rope(k)
and, on int8 pools, the per-token K/V absmax rows in ONE pass over the
projection outputs; one Pallas kernel on TPU, a bit-identical XLA
reference on CPU), with the cos/sin tables built once per step
(``rope_tables_for_positions``) instead of once per layer.  The
quantized writes consume the epilogue's absmax rows instead of
re-reading k/v.
"""
from __future__ import annotations

import math
import re
import time
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..core.tensor import Tensor
from .spmd import (TPContext, tp_embed, tp_gather_logits,
                   tp_gather_logits_q8, tp_serving_context)

__all__ = ["MixedStep", "copy_block", "extract_blocks", "inject_blocks",
           "migration_compiles", "migration_transfers", "STEP_SCOPES",
           "hlo_op_scopes"]

# The ``jax.named_scope`` names inside the traced step bodies (and the
# ops they call: ``ops/pallas_kernels._ragged_paged_attention_pallas``,
# ``ops/moe_gate.moe_ffn``).  No layer index: layers aggregate, and a
# later ``lax.scan`` over layers keeps the names.  A device op belongs
# to the INNERMOST of these on its ``op_name`` path (``ffn`` holds a
# MoE layer's norm and residual, ``moe.*`` what lies inside it).
STEP_SCOPES = frozenset((
    "embed", "attn.qkv", "attn.rope", "attn.kv_write", "attn.kernel",
    "attn.out", "ffn", "moe.gate", "moe.dispatch", "moe.experts",
    "moe.combine", "ep.all_to_all", "lm_head", "sample",
    # a latent-attention layer (MLA) and a held share of routed experts
    "attn.q_lora", "attn.kv_latent", "attn.absorb", "attn.unabsorb",
    "moe.shared", "moe.sort"))

# Kernels XLA:TPU makes itself and names itself (their ``op_name`` is the
# kernel's, the path of scopes is gone): instruction-name prefix -> scope.
# ``jax.lax.ragged_dot`` becomes ``ragged-dot-metadata`` (tile tables from
# the group sizes) and ``ragged-dot-none`` (the grouped matmul).  Only a
# step built with ``use_pallas=False`` holds one on a TPU: the Pallas
# grouped product (``grouped_expert_matmul``) keeps its scope path.
_XLA_KERNEL_SCOPES = {"ragged-dot": "moe.experts"}

_HLO_INSTR = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_HLO_OPERAND = re.compile(r"\(%([\w.\-]+)")


def hlo_op_scopes(hlo_text: str) -> Dict[str, Optional[str]]:
    """``{instruction name: scope}`` for every instruction of an
    OPTIMIZED HLO module's text (``compiled.as_text()``): the scope is
    the innermost ``STEP_SCOPES`` name on the instruction's
    ``metadata op_name`` path, ``None`` where the path holds none.  A
    profiler trace names each device event by its instruction, so this
    is what turns ``fusion.12`` into ``moe.experts``.  An instruction
    the compiler made itself (no metadata: a fusion of bitcasts, a
    layout copy) takes its called computation's root's scope, else
    that of any instruction inside it, else its first operand's."""
    own, calls, operand, body, root = {}, {}, {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = _HLO_INSTR.match(line)
        if m is None:
            if line.endswith("{") and not line.startswith(" "):
                comp = line.split("(", 1)[0].split()[-1].lstrip("%")
            continue
        name = m.group(1)
        body.setdefault(comp, []).append(name)
        if line.lstrip().startswith("ROOT "):
            root[comp] = name
        meta = _HLO_OP_NAME.search(line)
        own[name] = next(
            (part for part in reversed(meta.group(1).split("/"))
             if part in STEP_SCOPES), None) if meta else None
        if own[name] is None:
            own[name] = next((s for prefix, s in _XLA_KERNEL_SCOPES.items()
                              if name.startswith(prefix)), None)
        c = _HLO_CALLS.search(line)
        if c:
            calls[name] = c.group(1)
        o = _HLO_OPERAND.search(line[m.end():])
        if o:
            operand[name] = o.group(1)

    out: Dict[str, Optional[str]] = {}

    def resolve(name, depth=0):
        if name in out or name not in own:
            return out.get(name)
        scope = own[name]
        if scope is None and name in calls:
            inner = calls[name]
            scope = own.get(root.get(inner)) or next(
                (own[i] for i in body.get(inner, ()) if own[i]), None)
        if scope is None and name in operand and depth < 8:
            scope = resolve(operand[name], depth + 1)
        out[name] = scope
        return scope

    for name in own:
        resolve(name)
    return out


def _resolve_tp(model, mesh, sharding, tp: Optional[TPContext]
                ) -> Optional[TPContext]:
    """Step-constructor tp plumbing: a prebuilt shared context wins;
    otherwise resolve mesh+config here (standalone step construction).
    None = single-chip."""
    if tp is not None:
        return tp
    if mesh is None and sharding is None:
        return None
    return tp_serving_context(model, mesh, sharding)


def _inner_model(model):
    """The decoder stack behind a CausalLM wrapper — ``model.llama``
    (dense) or ``model.mixtral`` (MoE, round 24).  Both expose the same
    ``embed_tokens / layers / norm`` surface, which is everything the
    traced bodies touch; per-layer FFN dispatch branches on the LAYER
    (``block_sparse_moe`` vs ``mlp``), not the wrapper."""
    for name in ("llama", "mixtral", "deepseek"):
        inner = getattr(model, name, None)
        if inner is not None:
            return inner
    raise ValueError(
        "serving steps need a LlamaForCausalLM-shaped model (an inner "
        ".llama, .mixtral or .deepseek decoder stack); got %r"
        % type(model).__name__)


def _step_body(module, default: str) -> str:
    """The per-layer function of the step that a layer's attention or
    FFN module asks for: its own ``serving_body`` attribute (declared
    by the module's class beside what that body needs of it, as
    ``latent_row`` is), else ``default``.  A new kind of layer is a new
    name and a new body (ROADMAP D3), not a branch inside another's."""
    return getattr(module, "serving_body", default)


def latent_attention(model):
    """The model's first attention module that caches ONE latent row a
    token (``serving_body`` "mla": ``latent_row`` columns, no kv-head
    axis), else ``None``.  Whatever moves or converts K/V pages refuses
    such a model."""
    for layer in _inner_model(model).layers:
        at = getattr(layer, "self_attn", None)
        if _step_body(at, "gqa") == "mla":
            return at
    return None


def _embed(llama, tokens, tp: Optional[TPContext]) -> Tensor:
    """The traced body's embedding lookup: the module's gather
    single-chip (and pure-fsdp, whose params are full after the
    prologue gather), the vocab-parallel masked lookup + exact psum
    under tp.  ``tokens`` already carries the body's batch shape."""
    if tp is None or tp.axis is None:
        return llama.embed_tokens(Tensor._from_value(tokens))
    return Tensor._from_value(tp_embed(
        llama.embed_tokens.weight._value, tokens, tp.axis))


def _tp_psum(t: Tensor, tp: Optional[TPContext]) -> Tensor:
    """The layer-boundary collective: identity single-chip, psum of the
    row-sharded projection's partial sums over the tp axis otherwise.
    (The ONE place the per-layer collective lives — the spot a
    quantized all-reduce would drop into.)"""
    if tp is None or tp.axis is None:
        return t
    return Tensor._from_value(jax.lax.psum(t._value, tp.axis))


def _moe_ffn(blk, h2: Tensor, tp: Optional[TPContext], real,
             loads: Optional[list], use_pallas=None) -> Tensor:
    """The fused dropless MoE FFN of a bank that holds every expert of
    its router, traced into the step body in place of ``layer.mlp``
    (``ops.moe_gate.moe_ffn``): the shared top-k gate over the block's
    tokens, then on one chip the assignments sorted by expert and
    multiplied by a grouped product sized by the rows each expert
    really has, un-sorted and summed over the k.  ``real [T]`` marks
    the pack's real tokens: its padding is given to no expert and
    counted in no load; the rows each expert was given go to
    ``loads``.  ``use_pallas`` is the step's: the grouped product is
    the Pallas kernel or XLA's.  Under an ``ep`` mesh axis the gate's
    assignments are scattered into per-expert buffers sized so that
    none drops, which cross the axis as two ``all_to_all`` exchanges
    plus one token-stripe ``all_gather``, and no load is counted.

    No ``_tp_psum`` boundary here: the combine output is the FULL
    activation (each assignment contributes exactly one expert's
    output), already replicated across tp — the expert banks never
    shard over tp."""
    from ..ops.moe_gate import moe_ffn
    ep_axis = tp.ep_axis if tp is not None else None
    ep_deg = tp.ep_degree if tp is not None else 1
    v = h2._value
    flat = v.reshape(-1, v.shape[-1])
    out, load = moe_ffn(flat, blk.gate.weight._value, blk.w_gate._value,
                        blk.w_up._value, blk.w_down._value,
                        top_k=blk.top_k, ep_axis=ep_axis,
                        ep_degree=ep_deg, valid=real,
                        use_pallas=use_pallas)
    if loads is not None and load is not None:
        loads.append(load)
    return Tensor._from_value(out.reshape(v.shape))


def _held_moe_ffn(blk, h2: Tensor, real, loads: Optional[list],
                  use_pallas=None) -> Tensor:
    """A bank that holds a SHARE of its router's experts, with shared
    experts beside it (``ops.moe_gate.moe_ffn_held``: group-limited
    routing over the router's full width, the held assignments sorted
    and multiplied by a grouped product sized by real rows).  ``real
    [T]`` marks the pack's real tokens: its padding is given to no
    expert and counted in no load.  The rows each held expert was
    given go to ``loads``."""
    v = h2._value
    with jax.named_scope("moe.shared"):
        shared = blk.shared_experts(h2)
    out, load = blk.routed(v.reshape(-1, v.shape[-1]), real,
                           use_pallas=use_pallas)
    if loads is not None:
        loads.append(load)
    return shared + Tensor._from_value(out.reshape(v.shape))


def _ffn_module(layer):
    blk = getattr(layer, "block_sparse_moe", None)
    return layer.mlp if blk is None else blk


def _ffn(layer, h2: Tensor, tp: Optional[TPContext], real=None,
         loads: Optional[list] = None, use_pallas=None) -> Tensor:
    """Per-layer FFN dispatch of the traced body, by the body the
    layer's FFN module declares (``_step_body``): the MoE
    whose bank holds every expert of its router, the MoE that holds a
    share of them, and for a module that declares none its own forward
    (a Megatron-sharded dense MLP) with its psum boundary.  ``real``
    and ``loads`` are the MoE bodies' (the pack's real rows in, the
    rows each expert was given out), ``use_pallas`` their grouped
    product's lowering."""
    blk = _ffn_module(layer)
    body = _step_body(blk, "dense")
    if body == "moe_full_bank":
        return _moe_ffn(blk, h2, tp, real, loads, use_pallas)
    if body == "moe_held":
        return _held_moe_ffn(blk, h2, real, loads, use_pallas)
    return _tp_psum(blk(h2), tp)


def _real_rows(T: int, q_offsets, q_lens):
    """bool ``[T]``: the rows of a budget-``T`` pack that some span
    owns (the rest is the pack's padding)."""
    tok = jnp.arange(T, dtype=jnp.int32)[:, None]
    first = q_offsets.astype(jnp.int32)[None, :]
    return jnp.any((tok >= first) & (tok < first + q_lens[None, :]),
                   axis=1)


def _grouped_product_experts(model, tp: Optional[TPContext]):
    """``(experts, top_k)`` of the model's first FFN module whose step
    body holds the sorted grouped product
    (``ops.moe_gate.sorted_expert_swiglu``: a held share always, a full
    bank everywhere but on an ``ep`` axis): the experts in its bank and
    the assignments a token makes; ``(0, 0)`` where no layer's does.
    A step that holds one reports the rows its experts were given, and
    is traced through :func:`_traced_x64_off`."""
    bodies = ("moe_held",) if tp is not None and tp.ep_degree > 1 \
        else ("moe_held", "moe_full_bank")
    for layer in _inner_model(model).layers:
        blk = _ffn_module(layer)
        if _step_body(blk, "dense") in bodies:
            return int(blk.w_gate.shape[0]), int(blk.top_k)
    return 0, 0


def _traced_x64_off(step):
    """A step with a grouped product is traced with x64 off, its
    operands being 32-bit, whichever lowering the product has: XLA:TPU's
    pass that rewrites 64-bit element types does not know ragged-dot
    and stops at one in a module that holds any (the argmax's i64 is
    enough), and the Pallas kernel's scalar tables are int32."""
    def traced(*args):
        with jax.enable_x64(False):
            return step(*args)
    return traced


def _shapes_on(args, device_sharding):
    """``args`` as ``ShapeDtypeStruct``s that carry ``device_sharding``:
    what ``aot_lower`` hands ``lower`` to compile for another device
    than the one the arrays live on."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=device_sharding), args)


def _tp_logits(logits: Tensor, tp: Optional[TPContext],
               q8: bool = False) -> Tensor:
    """Identity single-chip; the vocab-shard all-gather under tp, so
    the on-device argmax sees the full vocab row.  ``q8`` swaps in the
    EQuARX-style int8 gather (``spmd.tp_gather_logits_q8``) — ~4× less
    interconnect payload, tolerance-gated instead of exact."""
    if tp is None or tp.axis is None:
        return logits
    if q8:
        return Tensor._from_value(
            tp_gather_logits_q8(logits._value, tp.axis))
    return Tensor._from_value(tp_gather_logits(logits._value, tp.axis))


def _cp_local_dest(dest_blocks, dest_offsets, bsl, cp_axis, sink):
    """Translate GLOBAL per-token write destinations into this chip's
    slot stripe (round 22, traced inside the shard_map body).

    Under cp the pool's block_size dim is striped: chip ``r`` holds
    slots ``[r*bsl, (r+1)*bsl)`` of every page, where ``bsl`` is the
    LOCAL shard's slot count (``block_size/cp``).  A token whose global
    in-page offset falls in this chip's stripe writes at the local
    offset; every other chip routes that token to its OWN sink-page
    stripe (the same garbage-absorbing page padding already uses), so
    one scatter per chip writes each K/V row exactly once pool-wide.
    """
    r = jax.lax.axis_index(cp_axis)
    lo = dest_offsets - r * bsl
    owned = (lo >= 0) & (lo < bsl)
    n = dest_offsets.shape[0]
    blk = jnp.where(owned, dest_blocks, jnp.int32(sink))
    off = jnp.where(owned, lo, jnp.arange(n, dtype=jnp.int32) % bsl)
    return blk, off


def _samp_knobs(samp):
    """Decode a packed per-row sampling operand ``[..., 4]`` int32 into
    ``(temps f32, top_ks i32, top_ps f32, seeds i32)``.  Temperature
    and top-p ride BITCAST in the int32 lane, so one dtype-uniform
    buffer carries every knob and the packed host transfer stays a
    single int32 array."""
    t = jax.lax.bitcast_convert_type(samp[..., 0], jnp.float32)
    p = jax.lax.bitcast_convert_type(samp[..., 2], jnp.float32)
    return t, samp[..., 1], p, samp[..., 3]


def _materialize_params(params, dtype):
    """Dequant-on-use prologue shared by every traced step body: a
    serving-PTQ tree (int8 weights + ``::scale`` vectors) comes back as
    the fp dict ``bind_state`` expects, with the dequant traced INTO
    the step so XLA fuses it into the consuming matmuls and HBM keeps
    only the int8 tree.  A plain fp tree passes through untouched (the
    default path's trace is unchanged)."""
    from ..quantization.functional import (dequantize_param_tree,
                                           is_weight_scale_key)
    if not any(is_weight_scale_key(k) for k in params):
        return params
    return dequantize_param_tree(params, dtype)


def _step_params(param_tensors, tp: Optional[TPContext], qtree=None):
    """The params operand for one step call: plain values single-chip;
    under tp the context's ONE placed (sharded) copy — so the jit's
    in_shardings alias instead of resharding, and placement happens
    once per engine, not per step or per call.  ``qtree`` (the
    serving-PTQ int8+scales tree) replaces the live model values when
    weight quantization is on — it is device-resident and immutable,
    so steady state is pointer-identical."""
    vals = qtree if qtree is not None \
        else {k: t._value for k, t in param_tensors.items()}
    if tp is None:
        return vals
    return tp.place_params(vals)


def _cache_scales(caches, quant_kv: bool):
    """The per-layer scale-table operands: empty tuples for fp pools,
    so the default path's pytree — and therefore its compiled module —
    is byte-identical to the pre-quantization steps."""
    if not quant_kv:
        return (), ()
    return (tuple(c.key_scale for c in caches),
            tuple(c.value_scale for c in caches))


def _rebind_caches(caches, new_kcs, new_vcs, new_kss, new_vss):
    """Rebind the donated pool (and scale, when quantized) arrays onto
    their PagedKVCache owners after a step."""
    for i, (c, kc, vc) in enumerate(zip(caches, new_kcs, new_vcs)):
        c.key_cache = kc
        c.value_cache = vc
        if new_kss:
            c.key_scale = new_kss[i]
            c.value_scale = new_vss[i]


def _ensure_quant_specs(tp: Optional[TPContext], qtree) -> None:
    """Register the PTQ tree's ``::scale`` keys in the shared context's
    spec table (idempotent — the engine's steps share one TPContext)
    and reject an incompatible layout up front: a column-sharded
    weight's scale vector must itself split by tp."""
    if tp is None or qtree is None:
        return
    from .spmd import llama_param_specs, mixtral_param_specs
    missing = [k for k in qtree if k not in tp.specs]
    if missing:
        specs_fn = mixtral_param_specs if any(
            "block_sparse_moe" in k for k in qtree) else llama_param_specs
        tp.specs.update(specs_fn(
            missing, tp.layout,
            shapes={k: tuple(qtree[k].shape) for k in missing},
            mesh=tp.mesh))
    for k, v in qtree.items():
        spec = tp.specs[k]
        if v.ndim == 1 and tuple(spec) and spec[0] is not None \
                and v.shape[0] % tp.degree:
            raise ValueError(
                "quantized weights are incompatible with this tp spec: "
                "scale vector %r has %d channels, not divisible by the "
                "tp degree %d (spec %s)"
                % (k, v.shape[0], tp.degree, spec))


def _named(fn, name: str):
    """``fn`` under the name its compiled module should carry: a
    profiler trace prints ``jit_<name>(<fingerprint>)`` per launch."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _wrap_sharded(step, tp: TPContext, params_dict, n_layers: int,
                  n_repl: int, donate, quant_kv: bool = False):
    """Wrap a serving-step body as the explicit SPMD program: shard_map
    over the mesh (params by family spec — including int8 weights
    and their scale vectors, the ``n_repl`` host operands replicated,
    per-layer KV pools head-sharded with their absmax tables when
    quantized) under a jit whose in/out shardings pin the placed
    layouts — donation of the pools carries through, so the cache
    append stays an in-place HBM update on every chip.

    2D mesh (round 21): when the context carries an fsdp axis, the
    params enter in their fsdp×tp STORAGE placement (the same one the
    2D train step produces — zero re-sharding) and a prologue
    all-gathers each fsdp-sharded param back to its tp compute shard
    before the unchanged body runs; pools and host operands never name
    fsdp, so they replicate across it (and across any extra replica
    axis) for free."""
    from ..core.jax_compat import shard_map_compat
    from .spmd import fsdp_gather
    repl = PartitionSpec()
    pspecs = {k: tp.specs[k] for k in params_dict}
    pools = (tp.layout.kv_pool(),) * n_layers
    spools = (tp.layout.kv_scale(),) * n_layers if quant_kv else ()
    in_specs = (pspecs,) + (repl,) * n_repl + (pools, pools,
                                               spools, spools)
    out_specs = (repl, pools, pools, spools, spools)
    if tp.fsdp_axis is not None:
        inner, faxis = step, tp.fsdp_axis

        def step(params, *rest):                       # noqa: F811
            params = {k: fsdp_gather(v, pspecs[k], faxis)
                      for k, v in params.items()}
            return inner(params, *rest)
        _named(step, inner.__name__)
    fn = shard_map_compat(step, tp.mesh, in_specs=in_specs,
                          out_specs=out_specs)
    return jax.jit(fn, donate_argnums=donate,
                   in_shardings=tp.named(in_specs),
                   out_shardings=tp.named(out_specs))


def _copy_block_impl(kcs, vcs, src, dst):
    return (tuple(kc.at[dst].set(kc[src]) for kc in kcs),
            tuple(vc.at[dst].set(vc[src]) for vc in vcs))


def _copy_block_q8_impl(kcs, vcs, kss, vss, src, dst):
    """Quantized pools: the page's int8 codes AND its per-head absmax
    row move together — a copied page dequantizes identically to its
    source, so copy-on-write never changes what a reader sees."""
    return (tuple(kc.at[dst].set(kc[src]) for kc in kcs),
            tuple(vc.at[dst].set(vc[src]) for vc in vcs),
            tuple(ks.at[dst].set(ks[src]) for ks in kss),
            tuple(vs.at[dst].set(vs[src]) for vs in vss))


# copy-on-write for a shared prefix page: ONE donated dispatch copies the
# page across every layer's pool; src/dst are traced scalars (no
# recompile per page id)
_copy_block_j = jax.jit(_copy_block_impl, donate_argnums=(0, 1))
_copy_block_q8_j = jax.jit(_copy_block_q8_impl,
                           donate_argnums=(0, 1, 2, 3))


def copy_block(caches, src: int, dst: int):
    """Copy physical page ``src`` to ``dst`` in every layer's K/V pool
    (rebinds the PagedKVCache arrays in place; an int8 pool's scale
    rows travel with their pages)."""
    kcs = tuple(c.key_cache for c in caches)
    vcs = tuple(c.value_cache for c in caches)
    if getattr(caches[0], "quantized", False):
        kss = tuple(c.key_scale for c in caches)
        vss = tuple(c.value_scale for c in caches)
        new_k, new_v, new_ks, new_vs = _copy_block_q8_j(
            kcs, vcs, kss, vss, jnp.asarray(src, jnp.int32),
            jnp.asarray(dst, jnp.int32))
        for c, kc, vc, ks, vs in zip(caches, new_k, new_v, new_ks,
                                     new_vs):
            c.key_cache = kc
            c.value_cache = vc
            c.key_scale = ks
            c.value_scale = vs
        return
    new_k, new_v = _copy_block_j(kcs, vcs, jnp.asarray(src, jnp.int32),
                                 jnp.asarray(dst, jnp.int32))
    for c, kc, vc in zip(caches, new_k, new_v):
        c.key_cache = kc
        c.value_cache = vc


# ---------------------------------------------------------------------------
# KV page migration (round 19): extract_blocks / inject_blocks
# ---------------------------------------------------------------------------
# The packed-operand lesson (r11: a host transfer costs ~a whole
# compiled tiny-model module on CPU — transfer COUNT is the budget)
# applied to page movement: a migration is ONE batched device gather
# whose stacked result crosses device→host in ONE copy per dtype
# (int8 codes + their fp32 scale rows), and an injection is ONE donated
# scatter dispatch whose buffer crosses host→device as one operand per
# dtype — never a per-page / per-layer copy loop.  Page counts pad to a
# pow2 bucket (extract: repeat a real page, sliced off on the host;
# inject: padding routed to the sink page) so compiles stay bounded by
# pool geometry × the log2 bucket set — counted in MIGRATION_COMPILES
# and gated like every other step's compile budget.

MIGRATION_COMPILES = {"extract": 0, "inject": 0}
MIGRATION_TRANSFERS = {"d2h": 0, "h2d": 0}
_MIG_SEEN = set()


def migration_compiles():
    """Snapshot of {extract, inject} trace counts (one per pool
    geometry × pow2 page bucket — the compile-bound gate's source)."""
    return dict(MIGRATION_COMPILES)


def migration_transfers():
    """Snapshot of {d2h, h2d} host payload-copy counts.  Each extract
    adds 1 (fp pools) or 2 (int8: codes + scales) d2h copies; each
    inject the same h2d — O(1) per migration, independent of the page
    count (the bench gate)."""
    return dict(MIGRATION_TRANSFERS)


def _note_mig_compile(kind: str, key: tuple):
    if key not in _MIG_SEEN:
        _MIG_SEEN.add(key)
        MIGRATION_COMPILES[kind] += 1


def _pow2_pages(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _extract_impl(kcs, vcs, ids):
    return jnp.stack([kc[ids] for kc in kcs]
                     + [vc[ids] for vc in vcs])


def _extract_q8_impl(kcs, vcs, kss, vss, ids):
    codes = jnp.stack([kc[ids] for kc in kcs]
                      + [vc[ids] for vc in vcs])
    scales = jnp.stack([ks[ids] for ks in kss]
                       + [vs[ids] for vs in vss])
    return codes, scales


# pure reads — the pools stay valid (extraction happens BEFORE the
# refcounted release on the source engine)
_extract_j = jax.jit(_extract_impl)
_extract_q8_j = jax.jit(_extract_q8_impl)


def _inject_impl(kcs, vcs, codes, ids):
    L = len(kcs)
    return (tuple(kc.at[ids].set(codes[i].astype(kc.dtype))
                  for i, kc in enumerate(kcs)),
            tuple(vc.at[ids].set(codes[L + i].astype(vc.dtype))
                  for i, vc in enumerate(vcs)))


def _inject_q8_impl(kcs, vcs, kss, vss, codes, scales, ids):
    L = len(kcs)
    return (tuple(kc.at[ids].set(codes[i]) for i, kc in enumerate(kcs)),
            tuple(vc.at[ids].set(codes[L + i])
                  for i, vc in enumerate(vcs)),
            tuple(ks.at[ids].set(scales[i])
                  for i, ks in enumerate(kss)),
            tuple(vs.at[ids].set(scales[L + i])
                  for i, vs in enumerate(vss)))


# donated: injection is an in-place HBM write into the target pools,
# exactly like the cache appends (hlo-donation covers this module too)
_inject_j = jax.jit(_inject_impl, donate_argnums=(0, 1))
_inject_q8_j = jax.jit(_inject_q8_impl, donate_argnums=(0, 1, 2, 3))


def extract_blocks(caches, block_ids, n_tokens: int):
    """Serialize physical pages ``block_ids`` out of every layer's pool
    into one contiguous host :class:`~paddle_tpu.ops.paged_attention.
    KVPageBuffer` — ONE batched gather dispatch, ONE device→host copy
    per dtype (int8 codes plus their per-page ``key_scale``/
    ``value_scale`` rows, which live per physical page and travel
    free).  The pools are only read; release the pages through the
    refcounted ``free_sequence`` afterwards."""
    from ..ops.paged_attention import KVPageBuffer
    c0 = caches[0]
    ids = [int(b) for b in block_ids]
    if not ids:
        raise ValueError("extract_blocks needs at least one page")
    n = len(ids)
    n_pad = _pow2_pages(n)
    idv = np.full((n_pad,), ids[0], np.int32)   # pad: re-gather a real
    idv[:n] = ids                               # page, sliced off below
    kcs = tuple(c.key_cache for c in caches)
    vcs = tuple(c.value_cache for c in caches)
    quant = bool(getattr(c0, "quantized", False))
    _note_mig_compile("extract", ("x", len(caches), n_pad,
                                  c0.page_geometry()))
    if quant:
        kss = tuple(c.key_scale for c in caches)
        vss = tuple(c.value_scale for c in caches)
        codes_d, scales_d = _extract_q8_j(kcs, vcs, kss, vss, idv)
        codes = np.asarray(codes_d)
        scales = np.ascontiguousarray(np.asarray(scales_d)[:, :n])
        MIGRATION_TRANSFERS["d2h"] += 2
    else:
        codes = np.asarray(_extract_j(kcs, vcs, idv))
        scales = None
        MIGRATION_TRANSFERS["d2h"] += 1
    return KVPageBuffer(
        codes=np.ascontiguousarray(codes[:, :n]), scales=scales,
        n_pages=n, n_tokens=int(n_tokens), block_size=c0.block_size,
        num_kv_heads=c0.num_kv_heads, head_dim=c0.head_dim,
        num_layers=len(caches), kv_dtype=c0.kv_dtype)


def inject_blocks(caches, buf, dest_blocks):
    """Scatter a :class:`KVPageBuffer`'s pages into ``dest_blocks`` of
    every layer's pool — ONE donated dispatch, the buffer crossing
    host→device as one operand per dtype.  ``dest_blocks`` must come
    from the target pool's refcounted ``allocate_block`` path (the
    caller owns the references).  Geometry (layer count, page shape,
    ``kv_dtype``) must match the buffer's header exactly — a mismatch
    (e.g. int8 pages into an fp32 pool) raises a clear ValueError here,
    never a dtype failure inside the trace."""
    c0 = caches[0]
    here = (len(caches),) + c0.page_geometry()
    want = buf.geometry()
    if here != want:
        raise ValueError(
            "inject_blocks: pool geometry mismatch — buffer was "
            "extracted from (layers, block_size, kv_heads, head_dim, "
            "kv_dtype)=%r but the target pool is %r; KV pages only "
            "migrate between engines with identical pool geometry "
            "(including kv_dtype — int8 codes are meaningless in an "
            "fp pool and vice versa)" % (want, here))
    n = buf.n_pages
    if len(dest_blocks) != n:
        raise ValueError(
            "inject_blocks: buffer holds %d page(s) but %d destination "
            "block(s) were allocated" % (n, len(dest_blocks)))
    n_pad = _pow2_pages(n)
    sink = getattr(c0, "sink", -1)
    pad_id = sink if sink >= 0 else int(dest_blocks[-1])
    idv = np.full((n_pad,), pad_id, np.int32)
    idv[:n] = [int(b) for b in dest_blocks]
    codes, scales = buf.codes, buf.scales
    if n_pad != n:
        # pad rows route to the sink page (or re-write the last page
        # with its own content) — garbage-on-garbage, like every other
        # fixed-shape padding in the serving steps
        rep = np.repeat(codes[:, -1:], n_pad - n, axis=1)
        codes = np.concatenate([codes, rep], axis=1)
        if scales is not None:
            srep = np.repeat(scales[:, -1:], n_pad - n, axis=1)
            scales = np.concatenate([scales, srep], axis=1)
    kcs = tuple(c.key_cache for c in caches)
    vcs = tuple(c.value_cache for c in caches)
    quant = bool(getattr(c0, "quantized", False))
    _note_mig_compile("inject", ("i", len(caches), n_pad,
                                 c0.page_geometry()))
    if quant:
        kss = tuple(c.key_scale for c in caches)
        vss = tuple(c.value_scale for c in caches)
        new_k, new_v, new_ks, new_vs = _inject_q8_j(
            kcs, vcs, kss, vss, codes, scales, idv)
        MIGRATION_TRANSFERS["h2d"] += 2
        for c, kc, vc, ks, vs in zip(caches, new_k, new_v, new_ks,
                                     new_vs):
            c.key_cache = kc
            c.value_cache = vc
            c.key_scale = ks
            c.value_scale = vs
        return
    new_k, new_v = _inject_j(kcs, vcs, codes, idv)
    MIGRATION_TRANSFERS["h2d"] += 1
    for c, kc, vc in zip(caches, new_k, new_v):
        c.key_cache = kc
        c.value_cache = vc


def compiled_cost_stats(lowered, tokens: int) -> dict:
    """FLOPs + byte traffic of ONE compiled serving-step module — the
    serving twin of ``TrainStep.compiled_stats`` (the round-9 MFU
    source).  ``tokens`` is the launch's packed token capacity (a
    budget-``T`` launch advances up to T real tokens; padding spans do
    sink-page work the
    device genuinely executes, so per-token numbers are the honest
    full-launch amortization).  XLA reports PER-DEVICE numbers, so the
    consumer divides by per-chip peak — never peak x device_count.
    Every field is best-effort: a backend without cost_analysis just
    yields fewer keys."""
    stats = {"tokens": int(tokens), "source": "cost_analysis"}
    compiled = lowered.compile()
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        for src, dst in (("flops", "flops"),
                         ("bytes accessed", "bytes_accessed")):
            if ca.get(src):
                stats[dst] = float(ca[src])
    except Exception:                                 # noqa: BLE001
        pass
    try:
        ma = compiled.memory_analysis()
        for attr, dst in (("temp_size_in_bytes", "temp_bytes"),
                          ("argument_size_in_bytes", "argument_bytes"),
                          ("output_size_in_bytes", "output_bytes")):
            v = getattr(ma, attr, None)
            if v:
                stats[dst] = int(v)
    except Exception:                                 # noqa: BLE001
        pass
    if tokens > 0:
        if stats.get("flops"):
            stats["flops_per_token"] = stats["flops"] / tokens
        if stats.get("bytes_accessed"):
            stats["hbm_bytes_per_token"] = \
                stats["bytes_accessed"] / tokens
    return stats


class MixedStep:
    """ONE compiled donated XLA module per TOTAL-TOKEN BUDGET that
    advances ANY admission mix — active decode slots and pending prefill
    chunks together — in a single launch (Ragged Paged Attention,
    arXiv:2604.15464).

    The engine packs its work into a ragged token batch: every running
    slot contributes a length-1 decode span, every prefilling slot a
    length-C chunk span, concatenated on the token axis and padded to
    the smallest budget in a small geometric set.  The traced body
    embeds the packed tokens, and per layer projects, applies RoPE at
    each token's GLOBAL position, scatters K/V into cache pages (padding
    routed to the sink page — ``write_ragged_kv``), and runs ragged
    paged attention (Pallas kernel on TPU, XLA gather reference on CPU)
    where each span attends causally over its own page list.  Each
    span's LAST VALID row is gathered before the LM head — the [T, V]
    logits block is never materialized — and greedy-sampled on device,
    so the step's only host traffic is one [max_spans] int32 fetch.

    Shape policy: every span descriptor (offset, length, kv length,
    page table, sample row, per-token write destination) is TRACED DATA;
    the only traced SHAPE is the token budget, so total compiles are
    bounded by the budget-set size across any occupancy/admission churn
    — there is no separate prefill/decode module split and no per-chunk
    engine round.  ``compile_counts`` maps budget -> trace count (tests
    and the bench gate on it).
    """

    def __init__(self, model, caches: List, bt_width: int,
                 max_spans: int,
                 use_pallas: Optional[bool] = None,
                 mesh=None, sharding=None,
                 tp: Optional[TPContext] = None,
                 weight_qparams=None, quant_collectives: bool = False,
                 sampling: bool = False, spec_k: int = 0,
                 return_probs: bool = False):
        from ..core.device import on_tpu
        self.model = model
        self.caches = caches
        self.cfg = model.config
        self.bt_width = bt_width
        self.max_spans = max_spans
        self.sampling = bool(sampling)
        self.spec_k = int(spec_k)
        self.return_probs = bool(return_probs)
        if self.return_probs and not self.sampling:
            raise ValueError(
                "MixedStep return_probs=True exists for the SAMPLED "
                "draft role (the verifier's residual needs the draft's "
                "filtered distribution); a greedy draft is a delta — "
                "construct with sampling=True or drop return_probs")
        if self.spec_k and self.return_probs:
            raise ValueError(
                "MixedStep cannot be verifier (spec_k) and draft "
                "(return_probs) at once")
        # span-row tail past the block-table columns: the 4 standard
        # descriptors, +1 n_draft column under spec, +4 bitcast
        # sampling-knob columns under sampling.  4 == the round-13
        # layout, so default packs are byte-identical.
        self.row_extra = (4 + (1 if self.spec_k else 0)
                          + (4 if self.sampling else 0))
        self.sink = caches[0].sink
        if self.sink < 0:
            raise ValueError("MixedStep needs a sink page "
                             "(PagedKVCache(sink_block=True)) to mask "
                             "budget-padding writes")
        if use_pallas is None:
            use_pallas = on_tpu()
        self.use_pallas = use_pallas
        self._tp = _resolve_tp(model, mesh, sharding, tp)
        if self.spec_k and self._tp is not None:
            raise ValueError(
                "speculative verification (spec_k) is single-chip: the "
                "draft engine runs unsharded, so a tensor-parallel "
                "verifier would mix placements — drop mesh/sharding or "
                "drop the draft")
        self._quant_kv = bool(getattr(caches[0], "quantized", False))
        if self._tp is not None and self._tp.cp_degree > 1 \
                and self._quant_kv:
            from .spmd import validate_cp_serving
            validate_cp_serving(self._tp.cp_degree,
                                caches[0].block_size, quantized_kv=True)
        # a latent-attention model (MLA): one pool a layer, one row a
        # token; what shards, quantizes or drafts K/V pages is not
        # taught that row
        self._latent = latent_attention(model)
        for li, (layer, c) in enumerate(zip(_inner_model(model).layers,
                                            caches)):
            at = getattr(layer, "self_attn", None)
            if (_step_body(at, "gqa") == "mla") != bool(
                    getattr(c, "latent", False)):
                raise ValueError(
                    "MixedStep: layer %d's pool does not fit its "
                    "attention: a latent-attention layer needs a "
                    "latent pool (PagedKVCache(latent_width=its "
                    "latent_row)), any other K and V pools" % li)
        if self._latent is not None:
            for on, what in ((self._tp is not None, "a mesh (tp/cp/ep)"),
                             (self.spec_k or self.return_probs,
                              "speculative decoding")):
                if on:
                    raise ValueError(
                        "MixedStep: %s is not taught the latent "
                        "(MLA) cache row" % what)
        # a step that holds the sorted grouped product reports the rows
        # its experts were given and the row tiles they took: [moe_rows,
        # moe_rows_top, moe_tiles, load x El] int32 behind the sampled
        # tokens, in the same fetch
        self._moe_experts, self._moe_top_k = _grouped_product_experts(
            model, self._tp)
        self.n_stats = 3 + self._moe_experts if self._moe_experts else 0
        self.last_stats = None         # the last call's tail (np int32)
        self._wq = weight_qparams
        self._q8_gather = bool(quant_collectives)
        _ensure_quant_specs(self._tp, weight_qparams)
        self._param_tensors = dict(model.state_dict())
        self._fns = {}                 # token budget -> jitted step
        self.compile_counts = {}       # token budget -> trace count
        # perf_counter instant at which the last call_packed's jitted
        # call RETURNED (work enqueued, nothing fetched yet)
        self.t_dispatch = 0.0

    @property
    def total_compiles(self) -> int:
        return sum(self.compile_counts.values())

    def collective_bytes(self, T: int):
        """Per-chip collective payload of one sharded step at budget
        ``T`` ({} when single-chip; see
        ``spmd.TPContext.collective_bytes``)."""
        if self._tp is None:
            return {}
        return self._tp.collective_bytes(self.cfg, T, self.max_spans,
                                         quant_gather=self._q8_gather)

    def attn_rows(self, T: int, q_lens) -> int:
        """The q rows per kv head (for a latent model: per cached row,
        i.e. tokens x heads) that a budget-``T`` launch carrying
        spans of lengths ``q_lens`` computes in each layer's attention.
        The Pallas launch computes whole tiles (``ops/pallas_kernels.
        ragged_attn_rows``: a decode span one tile, a chunk
        ceil(q_len / tile)); the XLA reference and the context-parallel
        partial compute every row of the budget."""
        from ..ops.pallas_kernels import (latent_attn_rows,
                                          ragged_attn_rows,
                                          ragged_tile_geometry)
        cfg = self.cfg
        if self._latent is not None:
            # one cached row for all heads: the rows are tokens x heads
            # (the Pallas launch walks whole sub-tiles of real tokens)
            H = cfg.num_attention_heads
            return latent_attn_rows(q_lens, H) if self.use_pallas \
                else T * H
        deg = self._tp.degree if self._tp is not None else 1
        H = cfg.num_attention_heads // deg
        Hkv = cfg.num_key_value_heads // deg
        if not self.use_pallas or (self._tp is not None
                                   and self._tp.cp_degree > 1):
            return T * (H // Hkv)
        cache = self.caches[0]
        tile, _ = ragged_tile_geometry(
            H, Hkv, cache.head_dim, cache.block_size, self.bt_width,
            "bfloat16" if cfg.dtype == "bfloat16" else "float32",
            cache.kv_dtype)
        return ragged_attn_rows(q_lens, tile, H // Hkv)

    def moe_tile_rows(self, T: int) -> int:
        """The rows of one row tile of a budget-``T`` step's grouped
        expert product (``ops/pallas_kernels.grouped_tile_rows``, from
        the ``T x top_k`` rows of its sorted buffer and the experts
        held); 0 where the step holds no such product.  The step counts
        the tiles its experts' rows take (``moe_tiles`` of the step
        record) at this size under either lowering; the Pallas launch
        visits exactly those."""
        if not self.n_stats:
            return 0
        from ..ops.pallas_kernels import grouped_tile_rows
        return grouped_tile_rows(T * self._moe_top_k, self._moe_experts)

    def attn_blocks(self, q_lens, kv_lens):
        """``(blocks, masked)``: the key blocks one layer's latent
        launch walks for spans of these lengths and how many of them
        take its masked body (``ops/pallas_kernels.
        latent_attn_blocks``).  ``(0, 0)`` where no such launch runs:
        a grouped-query model, the XLA reference."""
        if self._latent is None or not self.use_pallas:
            return 0, 0
        from ..ops.pallas_kernels import latent_attn_blocks
        return latent_attn_blocks(q_lens, kv_lens,
                                  self.caches[0].block_size, self.bt_width)

    def _build(self, T: int):
        from ..autograd.tape import no_grad
        from ..ops.paged_attention import (_ragged_attention_xla,
                                           _ragged_latent_attention_xla,
                                           write_ragged_kv,
                                           write_ragged_kv_q8,
                                           write_ragged_latent)
        from ..ops.pallas_kernels import (_ragged_latent_attention_pallas,
                                          rope_qkv_epilogue,
                                          rope_interleaved,
                                          rope_tables_for_positions)
        model = self.model
        cfg = self.cfg
        llama = _inner_model(model)
        tp = self._tp
        deg = tp.degree if tp is not None else 1
        # under tensor parallelism the traced body sees this chip's
        # LOCAL head shard: projections produce H/tp query and Hkv/tp
        # kv heads, and the (head-sharded) page pools match
        H = cfg.num_attention_heads // deg
        Hkv = cfg.num_key_value_heads // deg
        D = cfg.hidden_size // cfg.num_attention_heads
        scale = 1.0 / math.sqrt(D)
        use_pallas = self.use_pallas
        n_stats = self.n_stats
        moe_tile = self.moe_tile_rows(T)
        quant_kv = self._quant_kv
        q8_gather = self._q8_gather
        pdtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        cp_axis = tp.cp_axis if tp is not None else None
        cp_deg = tp.cp_degree if tp is not None else 1
        sink = self.sink
        if use_pallas and cp_deg <= 1:
            from ..ops.pallas_kernels import _ragged_paged_attention_pallas

        if cp_deg > 1:
            # context parallel (round 22): each chip attends over its
            # LOCAL slot stripe of every page with the partial-softmax
            # kernel variant, then the `(o, m, l)` triples merge across
            # the cp axis (ops/online_softmax.cross_chip_merge — one
            # all_gather of the three small rows).  XLA path only for
            # now: the per-stripe Pallas launch is the TPU follow-up.
            from ..ops.online_softmax import cross_chip_merge
            from ..ops.paged_attention import _ragged_attention_xla_partial

            def attn(q, kc, vc, bt, q_off, q_len, kv_len,
                     ks=None, vs=None):
                bsl = kc.shape[1]
                stripe = jax.lax.axis_index(cp_axis) * bsl
                with jax.named_scope("attn.kernel"):
                    o, m, l = _ragged_attention_xla_partial(
                        q, kc, vc, bt, q_off, q_len, kv_len, scale,
                        stripe, bsl * cp_deg)
                    return cross_chip_merge(o, m, l, cp_axis)
        else:
            def attn(q, kc, vc, bt, q_off, q_len, kv_len,
                     ks=None, vs=None):
                if use_pallas:
                    # the wrapper names its own attn.kernel scope
                    return _ragged_paged_attention_pallas(
                        q, kc, vc, bt, q_off, q_len, kv_len, scale,
                        key_scale=ks, value_scale=vs)
                with jax.named_scope("attn.kernel"):
                    return _ragged_attention_xla(q, kc, vc, bt, q_off,
                                                 q_len, kv_len, scale,
                                                 ks, vs)

        W = self.bt_width
        S = self.max_spans
        EX = self.row_extra
        sampling = self.sampling
        spec_k = self.spec_k
        return_probs = self.return_probs
        if sampling or spec_k:
            from ..ops.sampling import (filtered_probs, sample_logits,
                                        spec_verify)

        def step(params, pack, q_probs, kcs, vcs, kss, vss):
            self.compile_counts[T] = self.compile_counts.get(T, 0) + 1
            # unpack the single host buffer (free at trace level —
            # slices of a constant layout): rows 0-3 of the leading
            # [4, T] block are tokens / positions / dest block / dest
            # offset; the trailing [S, W+EX] block is the block table
            # columns then q_offset / q_len / kv_len / sample_row
            # (+ n_draft under spec, + the 4 bitcast sampling-knob
            # columns under sampling).  ONE device_put per step instead
            # of nine — transfer count, not byte count, is the
            # decode-parity budget at low occupancy.
            tok_tab = pack[:4 * T].reshape(4, T)
            span_tab = pack[4 * T:].reshape(S, W + EX)
            tokens = tok_tab[0]
            positions = tok_tab[1]
            dest_blocks = tok_tab[2]
            dest_offsets = tok_tab[3]
            if cp_deg > 1:
                # the host packs GLOBAL in-page offsets; each chip
                # keeps only the rows its slot stripe owns (the rest
                # go to its sink stripe) — the scatter itself and the
                # packed-operand layout are unchanged
                dest_blocks, dest_offsets = _cp_local_dest(
                    dest_blocks, dest_offsets, kcs[0].shape[1],
                    cp_axis, sink)
            bt = span_tab[:, :W]
            q_offsets = span_tab[:, W]
            q_lens = span_tab[:, W + 1]
            kv_lens = span_tab[:, W + 2]
            sample_rows = span_tab[:, W + 3]
            col = W + 4
            if spec_k:
                n_draft = span_tab[:, col]
                col += 1
            if sampling:
                s_t, s_k, s_p, s_sd = _samp_knobs(span_tab[:, col:col + 4])
            params = _materialize_params(params, pdtype)
            new_kcs, new_vcs = [], []
            new_kss, new_vss = [], []
            with model.bind_state(params), no_grad():
                with jax.named_scope("embed"):
                    x = _embed(llama, tokens[None, :], tp)     # [1, T, h]
                    if cfg.dtype == "bfloat16":
                        x = x.astype("bfloat16")
                # rope tables built ONCE per step and kind of
                # attention (positions are layer-invariant) and
                # consumed by every layer of that kind
                rope_tables = {}

                def rope_of(body, at):
                    if body not in rope_tables:
                        with jax.named_scope("attn.rope"):
                            rope_tables[body] = (
                                at.rope_tables(positions)
                                if body == "mla" else
                                rope_tables_for_positions(
                                    positions, D, cfg.rope_theta))
                    return rope_tables[body]

                def gqa_attention(layer, x, li, kc, vc):
                    """Grouped-query attention over K and V pools
                    (Llama, Mixtral): x plus the block's output, and
                    the pools (and their scales) as written."""
                    at = layer.self_attn
                    cos_t, sin_t = rope_of("gqa", at)
                    with jax.named_scope("attn.qkv"):
                        h = layer.input_layernorm(x)
                        q = at.q_proj(h).reshape([1, T, H, D])
                        k = at.k_proj(h).reshape([1, T, Hkv, D])
                        v = at.v_proj(h).reshape([1, T, Hkv, D])
                    # fused RoPE+QKV epilogue: rope(q), rope(k) and the
                    # quantize-on-write absmax rows in ONE pass over
                    # the projection outputs
                    with jax.named_scope("attn.rope"):
                        qv, kv_, k_amax, v_amax = rope_qkv_epilogue(
                            q._value[0], k._value[0], v._value[0],
                            cos_t, sin_t, with_amax=quant_kv,
                            use_pallas=use_pallas)
                    with jax.named_scope("attn.kv_write"):
                        if quant_kv:
                            kc, vc, ks, vs = write_ragged_kv_q8(
                                kv_, v._value[0], kc, vc, kss[li],
                                vss[li], dest_blocks, dest_offsets,
                                k_amax=k_amax, v_amax=v_amax)
                        else:
                            ks = vs = None
                            kc, vc = write_ragged_kv(
                                kv_, v._value[0], kc, vc,
                                dest_blocks, dest_offsets)
                    out = attn(qv, kc, vc, bt, q_offsets,
                               q_lens, kv_lens, ks, vs)
                    with jax.named_scope("attn.out"):
                        out = Tensor._from_value(
                            out.reshape(1, T, H * D))
                        x = x + _tp_psum(at.o_proj(out), tp)
                    return x, kc, vc, ks, vs

                def mla_attention(layer, x, li, kc, vc):
                    """Latent attention in its ABSORBED form over the
                    one pool of ``[c_kv | k_rope | 0]`` rows: the
                    cache is never expanded, whatever the span."""
                    at = layer.self_attn
                    cos_t, sin_t = rope_of("mla", at)
                    pad = at.latent_row - at.kv_lora - at.rope
                    with jax.named_scope("attn.q_lora"):
                        h = layer.input_layernorm(x)
                        q_nope, q_r = at.queries(h)
                    with jax.named_scope("attn.kv_latent"):
                        c_kv, k_r = at.latent(h)
                    with jax.named_scope("attn.rope"):
                        q_r = rope_interleaved(
                            q_r[0], cos_t[:, None, :], sin_t[:, None, :])
                        k_r = rope_interleaved(k_r[0], cos_t, sin_t)
                    wk, wv = at.kv_b()
                    with jax.named_scope("attn.absorb"):
                        qt = jnp.einsum("thn,chn->thc", q_nope[0], wk)
                        q_abs = jnp.concatenate(
                            [qt, q_r, jnp.zeros((T, H, pad), qt.dtype)],
                            axis=-1)
                    with jax.named_scope("attn.kv_write"):
                        rows = jnp.concatenate(
                            [c_kv[0], k_r,
                             jnp.zeros((T, pad), k_r.dtype)], axis=-1)
                        kc = write_ragged_latent(rows, kc, dest_blocks,
                                                 dest_offsets)
                    if use_pallas:
                        # the wrapper names its own attn.kernel scope
                        o_lat = _ragged_latent_attention_pallas(
                            q_abs, kc, bt, q_offsets, q_lens, kv_lens,
                            at.softmax_scale, at.kv_lora)
                    else:
                        with jax.named_scope("attn.kernel"):
                            o_lat = _ragged_latent_attention_xla(
                                q_abs, kc, bt, q_offsets, q_lens,
                                kv_lens, at.softmax_scale, at.kv_lora)
                    with jax.named_scope("attn.unabsorb"):
                        out = jnp.einsum("thc,chv->thv", o_lat, wv)
                    with jax.named_scope("attn.out"):
                        out = Tensor._from_value(
                            out.reshape(1, T, H * at.v_dim))
                        x = x + at.o_proj(out)
                    return x, kc, None, None, None

                attention = {"gqa": gqa_attention, "mla": mla_attention}
                loads = []      # [El] int32 a sorted MoE layer
                real = None
                if n_stats:
                    with jax.named_scope("moe.sort"):
                        real = _real_rows(T, q_offsets, q_lens)
                for li, (layer, kc, vc) in enumerate(
                        zip(llama.layers, kcs, vcs)):
                    body = attention[_step_body(layer.self_attn, "gqa")]
                    x, kc, vc, ks, vs = body(layer, x, li, kc, vc)
                    new_kcs.append(kc)
                    new_vcs.append(vc)
                    if quant_kv:
                        new_kss.append(ks)
                        new_vss.append(vs)
                    with jax.named_scope("ffn"):
                        h2 = layer.post_attention_layernorm(x)
                        x = x + _ffn(layer, h2, tp, real, loads,
                                     use_pallas)
                with jax.named_scope("lm_head"):
                    x = llama.norm(x)
                    # only each span's sampled rows reach the LM head:
                    # one row per span normally ([max_spans, 1, h] @
                    # [h, V]); under spec_k each span's K+1 verify rows
                    # plus its last-valid row ([S*(K+2), 1, h]) — the
                    # [T, V] logits block is never materialized either way
                    if spec_k:
                        vrow = (q_offsets[:, None]
                                + jnp.arange(spec_k + 1,
                                             dtype=jnp.int32)[None, :])
                        last = q_offsets + jnp.maximum(q_lens - 1, 0)
                        vrow = jnp.minimum(vrow, last[:, None])
                        rows_idx = jnp.clip(
                            jnp.concatenate([vrow, sample_rows[:, None]],
                                            axis=1).reshape(-1), 0, T - 1)
                    else:
                        rows_idx = sample_rows
                    rows = Tensor._from_value(
                        x._value[0][rows_idx][:, None, :])
                    if model.lm_head is None:
                        from ..ops.linalg import matmul
                        logits = matmul(rows, llama.embed_tokens.weight,
                                        transpose_y=True)
                    else:
                        logits = model.lm_head(rows)
                    logits = _tp_logits(logits, tp, q8=q8_gather)
            with jax.named_scope("sample"):
                lv = logits._value[:, 0, :].astype(jnp.float32)
                if spec_k:
                    # speculative verify: rows [:, :K+1] feed the
                    # accept/reject scan, row K+1 is the plain-span sample
                    lv3 = lv.reshape(S, spec_k + 2, -1)
                    didx = jnp.clip(
                        q_offsets[:, None] + 1
                        + jnp.arange(spec_k, dtype=jnp.int32)[None, :],
                        0, T - 1)
                    d_toks = tokens[didx]          # the spans' fed drafts
                    base_pos = kv_lens - q_lens + 1
                    if sampling:
                        q = jnp.stack(q_probs, axis=1)        # [S, K, V]
                        n_acc, e_v = spec_verify(
                            lv3[:, :spec_k + 1], d_toks, n_draft, s_t, s_k,
                            s_p, s_sd, base_pos, q)
                        e_p = sample_logits(lv3[:, spec_k + 1], s_t,
                                               s_k, s_p, s_sd, kv_lens)
                    else:
                        zf = jnp.zeros((S,), jnp.float32)
                        zi = jnp.zeros((S,), jnp.int32)
                        n_acc, e_v = spec_verify(
                            lv3[:, :spec_k + 1], d_toks, n_draft, zf, zi,
                            zf, zi, base_pos)
                        e_p = jnp.argmax(lv3[:, spec_k + 1],
                                         axis=-1).astype(jnp.int32)
                    nxt = jnp.where(n_draft > 0, e_v, e_p)
                    return (nxt, n_acc, tuple(new_kcs), tuple(new_vcs),
                            tuple(new_kss), tuple(new_vss))
                if sampling:
                    # counter = kv_len — the sampled token's global
                    # position, so seeded tokens agree across engines
                    # and batchings
                    nxt = sample_logits(lv, s_t, s_k, s_p, s_sd,
                                           kv_lens)
                else:
                    nxt = jnp.argmax(lv, axis=-1).astype(jnp.int32)
                if n_stats:
                    # rows the experts held here were given, summed over
                    # the layers: total, the fullest expert's, the row
                    # tiles they took, each expert's
                    load = sum(loads[1:], loads[0]).astype(jnp.int32)
                    tiles = sum(jnp.sum((ld + (moe_tile - 1)) // moe_tile)
                                for ld in loads).astype(jnp.int32)
                    nxt = jnp.concatenate(
                        [nxt, jnp.sum(load)[None], jnp.max(load)[None],
                         tiles[None], load])
                if return_probs:
                    return (nxt, filtered_probs(lv, s_t, s_k, s_p),
                            tuple(new_kcs), tuple(new_vcs),
                            tuple(new_kss), tuple(new_vss))
                return (nxt, tuple(new_kcs), tuple(new_vcs),
                        tuple(new_kss), tuple(new_vss))

        if n_stats:
            step = _traced_x64_off(step)
        if spec_k and sampling:
            fn, donate = step, (3, 4, 5, 6)
        else:
            # no draft-probs operand: same pytree as round 13 when
            # sampling/spec are both off
            def fn(params, pack, kcs, vcs, kss, vss):
                return step(params, pack, None, kcs, vcs, kss, vss)
            donate = (2, 3, 4, 5)
        fn = _named(fn, "mixed_step")
        if tp is None:
            return jax.jit(fn, donate_argnums=donate)
        return _wrap_sharded(fn, tp, self._wq or self._param_tensors,
                             len(self.caches), n_repl=1,
                             donate=donate,
                             quant_kv=self._quant_kv)

    def __call__(self, tokens, positions, dest_blocks, dest_offsets,
                 q_offsets, q_lens, kv_lens, block_tables,
                 sample_rows) -> np.ndarray:
        """tokens/positions/dest_*: [T] packed per-token arrays (T must
        be a configured budget); q_offsets/q_lens/kv_lens/sample_rows:
        [max_spans]; block_tables: [max_spans, bt_width].  Returns the
        [max_spans] int32 greedy samples (row i = span i's next token;
        padding spans and non-final chunks are discarded by the
        engine)."""
        T = int(np.asarray(tokens).shape[0])
        pack, tok_tab, span_tab = self.new_pack(T)
        tok_tab[0] = tokens
        tok_tab[1] = positions
        tok_tab[2] = dest_blocks
        tok_tab[3] = dest_offsets
        W = self.bt_width
        span_tab[:, :W] = block_tables
        span_tab[:, W] = q_offsets
        span_tab[:, W + 1] = q_lens
        span_tab[:, W + 2] = kv_lens
        span_tab[:, W + 3] = sample_rows
        return self.call_packed(pack, T)

    def new_pack(self, T: int):
        """Allocate the step's single host buffer: ``(pack, tok_tab,
        span_tab)`` where tok_tab [4, T] (rows tokens / positions /
        dest block / dest offset) and span_tab
        [max_spans, bt_width+row_extra] (block-table columns then
        q_offset / q_len / kv_len / sample_row, + n_draft under spec,
        + the 4 bitcast sampling-knob columns under sampling) are VIEWS
        into pack — fill them, then hand pack to ``call_packed``.  The
        extra tail columns come pre-zeroed (greedy, no drafts), so a
        caller that only fills the round-13 layout stays correct."""
        S, W = self.max_spans, self.bt_width
        pack = np.empty(4 * T + S * (W + self.row_extra), np.int32)
        span_tab = pack[4 * T:].reshape(S, W + self.row_extra)
        if self.row_extra > 4:
            span_tab[:, W + 4:] = 0
        return pack, pack[:4 * T].reshape(4, T), span_tab

    def aot_lower(self, T: int, device_sharding=None):
        """AOT-lower (never execute) one budget-``T`` module with a
        zero pack and the caches' current pools — the artifact the
        graftlint hlo-contract pass asserts over (donation aliases the
        pools, no f64 op, ONE packed int32 host operand of the pinned
        length).  Uses the same cached jit as ``call_packed``, so a
        subsequent real call does not re-trace.

        ``device_sharding`` lowers for ANOTHER device than the one the
        arrays live on: every operand becomes a ``ShapeDtypeStruct``
        carrying it.  With a compile-only TPU device
        (``jax.experimental.topologies``) ``.compile()`` then runs the
        real XLA:TPU + Mosaic compilers on a box with no chip —
        tests/test_tpu_compile.py."""
        fn = self._fns.get(T)
        if fn is None:
            fn = self._fns[T] = self._build(T)
        pack, _tok, _span = self.new_pack(T)
        pack[:] = 0
        params = _step_params(self._param_tensors, self._tp, self._wq)
        kcs = tuple(c.key_cache for c in self.caches)
        vcs = tuple(c.value_cache for c in self.caches)
        kss, vss = _cache_scales(self.caches, self._quant_kv)
        args = [params, jnp.asarray(pack)]
        if self.spec_k and self.sampling:
            V = self.cfg.vocab_size
            args.append(tuple(
                jnp.zeros((self.max_spans, V), jnp.float32)
                for _ in range(self.spec_k)))
        args += [kcs, vcs, kss, vss]
        if device_sharding is not None:
            args = _shapes_on(args, device_sharding)
        return fn.lower(*args)

    def compiled_stats(self, T: int) -> dict:
        """Cached ``cost_analysis`` of one budget-``T`` compiled mixed
        launch (see :func:`compiled_cost_stats`) — the capacity plane's
        per-token FLOPs/HBM source.  Reuses the ``call_packed`` jit
        cache, so a later real call does not re-trace."""
        cache = getattr(self, "_cost_stats", None)
        if cache is None:
            cache = self._cost_stats = {}
        if T not in cache:
            cache[T] = compiled_cost_stats(self.aot_lower(T), T)
        return cache[T]

    def call_packed(self, pack: np.ndarray, T: int, q_probs=None):
        """Dispatch one pre-packed step buffer (see ``new_pack``).  The
        nine per-step operands cross the host link as ONE int32
        device_put: transfer count, not byte count, is the budget at
        low occupancy.

        Returns the [max_spans] int32 sample array; a verifier
        (``spec_k``) returns ``(tokens, n_acc)`` and takes ``q_probs``
        (a tuple of K device-resident [max_spans, V] draft
        distributions) when sampled; a draft (``return_probs``)
        returns ``(tokens, probs)`` with probs left ON DEVICE."""
        with jax.profiler.TraceAnnotation("engine.dispatch"):
            fn = self._fns.get(T)
            if fn is None:
                fn = self._fns[T] = self._build(T)
            params = _step_params(self._param_tensors, self._tp,
                                  self._wq)
            kcs = tuple(c.key_cache for c in self.caches)
            vcs = tuple(c.value_cache for c in self.caches)
            kss, vss = _cache_scales(self.caches, self._quant_kv)
            args = [params, jnp.asarray(pack)]
            if self.spec_k and self.sampling:
                if q_probs is None:
                    raise ValueError(
                        "sampled speculative verify needs the draft's "
                        "q_probs tuple (zeros when no span drafts)")
                args.append(tuple(q_probs))
            out = fn(*args, kcs, vcs, kss, vss)
            n_out = 2 if self.spec_k or self.return_probs else 1
            _rebind_caches(self.caches, *out[n_out:])
        # the launch is enqueued: what follows is the wait for the
        # device (the step record's t_dispatch .. t_tokens)
        self.t_dispatch = time.perf_counter()
        with jax.profiler.TraceAnnotation("engine.fetch"):
            nxt = np.asarray(out[0])
            if self.n_stats:
                nxt, self.last_stats = (nxt[:self.max_spans],
                                        nxt[self.max_spans:])
            if self.spec_k:
                return nxt, np.asarray(out[1])
        if self.return_probs:
            return nxt, out[1]
        return nxt

    def op_scopes(self, T: int) -> Dict[str, Optional[str]]:
        """``{optimized-HLO instruction name: scope}`` of the compiled
        budget-``T`` module (see :func:`hlo_op_scopes`): what a trace
        reader needs to give each device op of a launch its part of
        the step.  Lowers through the ``call_packed`` jit cache and
        compiles through the compile cache, so on a budget that has
        run it builds nothing new."""
        return hlo_op_scopes(self.aot_lower(T).compile().as_text())
