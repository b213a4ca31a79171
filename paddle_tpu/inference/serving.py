"""Continuous batching over the paged KV cache.

Parity: the reference serving stack's batched multi-request execution —
block_multihead_attention
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu)
driven by a request scheduler around AnalysisPredictor
(paddle/fluid/inference/api/analysis_predictor.h:210 ZeroCopyRun).

TPU-native design: the scheduler keeps a fixed number of SLOTS and one
engine step is ONE jitted XLA module (``jit/serving_step.MixedStep``):
every step packs the whole admission mix — each running slot as a
length-1 decode span, each prefilling slot's next chunk as a span of up
to ``prefill_chunk_size`` tokens, as many chunks as the budget holds —
into one launch over the ragged paged attention kernel (Ragged Paged
Attention, arXiv:2604.15464) with all layers, the paged cache write,
the LM head and the sampler fused and the per-layer KV pools donated,
so the write is an in-place HBM update.  Total tokens pad to a small
geometric set of budgets and every span descriptor is traced data, so
compiles are bounded by the budget count whatever the admission and
eviction churn, a long prompt never stalls the running requests' TPOT,
and prefill pays no engine round of its own.

- **Chunked prefill**: a prompt longer than ``prefill_chunk_size``
  advances one chunk a step per prefilling slot, round-robin while the
  top budget has room.
- **Prefix cache** (``enable_prefix_cache``): refcounted KV pages plus
  a block-granularity prompt-prefix hash table
  (inference/prefix_cache.PrefixPageCache); an admitted request whose
  prefix hits shares those pages (refcount++, copy-on-write on the
  first partial page) and only prefills the suffix.  Eviction honors
  refcounts — a shared page is never reclaimed from under a live
  request's block table.
- **Stochastic sampling** (``sampling=True``): per-request temperature
  / top-k / top-p / seed ride ``add_request`` and reach the step as
  traced data (the pack grows four bitcast columns), sampled on device
  with a counter-based PRNG keyed on (request seed, token position) —
  so a sampled request's tokens are identical alone or batched,
  single-chip or tp, and changing knobs/seeds never retraces.
  ``temperature=0`` requests take the exact greedy argmax.
- **Speculative decoding** (``draft_model=``): a small draft model with
  its OWN per-layer paged pools — addressed by the SAME page ids, so
  allocation/refcount/COW bookkeeping is shared and prefix-cache hits
  carry draft KV for free — proposes ``spec_k`` tokens per engine round
  (k fused draft launches; prefill chunks mirror into the draft pool in
  the same launches), and the target verifies every slot's k+1
  positions in ONE MixedStep launch using length-(k+1) ragged spans.
  Standard accept/reject with rejection-resampling keeps the sampled
  output distribution exact; greedy speculative output is
  BYTE-IDENTICAL to non-speculative greedy.  Pages grown for rejected
  draft positions roll back through the refcounted release path (lazy
  mode).

Admission/eviction is host control flow; all math is jitted device
compute, and the only per-step host traffic is the one packed int32
operand in and the [slots] int32 next-token fetch out (a speculative
round adds the k [slots] draft-token fetches and the verifier's [slots]
accepted-count row — draft DISTRIBUTIONS stay on device).
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..observability.trace_merge import span_log
from ..ops.paged_attention import PagedKVCache

# the name of the one record every engine step writes to the process-wide
# ``observability.span_log`` (see ``ContinuousBatchingEngine.step``)
STEP_SPAN = "serving.step"

# process-wide engine-id sequence: a multi-engine router needs a stable
# identity per engine for health gauges / the /healthz payload, and an
# explicit engine_id= keeps ids meaningful across processes
_ENGINE_IDS = itertools.count()


@dataclass
class GenerationRequest:
    """One in-flight generation (parity: the request objects the
    reference serving runtime schedules)."""
    req_id: int
    prompt_ids: np.ndarray                 # [L] int
    max_new_tokens: int = 16
    eos_token_id: Optional[int] = None
    output_ids: List[int] = field(default_factory=list)
    state: str = "waiting"        # waiting -> [prefilling ->] running -> done
    # True when the engine ran out of KV pages mid-decode and finished
    # this request early instead of wedging the whole batch
    truncated: bool = False

    # slot bookkeeping (set while running)
    slot: int = -1
    seq_len: int = 0
    block_ids: List[int] = field(default_factory=list)
    # chunked-prefill progress: prompt tokens already in cache pages
    # (starts at the prefix-cache hit length)
    prefill_pos: int = 0
    # prompt tokens served from shared prefix pages instead of recompute
    prefix_hit_tokens: int = 0
    # stochastic sampling (round 14): temperature <= 0 is exact greedy;
    # seed feeds the per-position counter-based PRNG
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    seed: int = 0
    # n>1 generation groups: a child admits only after its parent's
    # prefill published the shared prefix pages (COW machinery)
    parent_req: Optional["GenerationRequest"] = field(default=None,
                                                     repr=False)
    # speculative decoding: positions [0, draft_len) hold draft-model
    # KV for the ACCEPTED token sequence
    draft_len: int = 0
    # telemetry marks (perf_counter): admission -> first token = TTFT,
    # first token -> done over n-1 tokens = TPOT
    t_submit: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0


class ContinuousBatchingEngine:
    """Slot scheduler around the fused mixed prefill+decode step, for a
    LlamaForCausalLM-shaped model (dense, Mixtral MoE, DeepSeek-V2 MLA).

    add_request() may be called at any time (including between steps
    while other requests are mid-decode); step() advances every running
    request by one token and every prefilling one by a chunk.  Greedy
    decoding — interleaved execution is bit-identical to running each
    request alone (the test contract).

    ``max_seq_len`` bounds prompt + generation per request and fixes the
    block-table width (a traced shape of the step); it defaults to the
    pool's fair share per slot, num_blocks * block_size //
    max_batch_size.

    ``prefill_chunk_size`` bounds a single prefill span (default: the
    pow2 ceil of ``max_seq_len``, capped at 512).  ``token_budgets``:
    ``"auto"`` is the geometric set covering all-decode up to
    slots+chunk, or an explicit tuple whose top must fit an all-decode
    pack; one module compiles per budget.

    ``mesh=`` (+ optional ``sharding=ShardingConfig(axis='tp')``)
    makes the engine multi-chip: the step runs tensor-parallel over the
    mesh's ``tp`` axis (see ``jit/spmd.py`` for the per-weight-family
    spec layout), with KV pools sharded over kv heads — per-chip pool
    HBM is 1/tp — and tokens byte-identical to the single-chip engine.
    A ``cp`` axis stripes every pool's slot dim, an ``ep`` axis shards
    the MoE expert banks.

    Quantization (tolerance-gated, not parity-gated: greedy token-match
    rate against the fp32 engine, per workload, against declared
    thresholds):

    - ``kv_dtype="int8"``: the paged pools store int8 codes plus
      per-page-per-head fp32 absmax scales — ~4× (fp32) / ~2× (bf16)
      pages per HBM byte, scales counted.  Writes quantize inside the
      compiled step, attention dequantizes into the same fp32
      online-softmax, COW/prefix sharing carry scales with pages.
    - ``weight_quant="int8"``: per-output-channel absmax PTQ over the
      projection weights (``quantization.functional.
      quantize_param_tree``); the step dequantizes on use, so HBM
      holds the int8 tree (+ scale vectors) — ~4× smaller weights.
    - ``quant_collectives=True`` (needs ``mesh``): the tp logits
      all-gather moves int8 codes + per-shard scales (EQuARX-style,
      arXiv:2506.17615) instead of fp words.

    Request tracing: the engine owns a bounded ``RequestTracer``
    (``tracer=`` kwarg; default ON, ``False`` = the no-op stub)
    recording typed per-request phase spans: enqueue, admit (+prefix
    hit), per-chunk prefill, sampled decode steps, first token,
    preempt, finish.  Host-side appends only, on the shared
    ``perf_counter`` clock; a ``ServingRouter`` merges every pool
    engine's spans into one fleet chrome trace (``fleet_trace``).

    KV page migration + disaggregation:
    ``extract_request``/``inject_request`` move a running request's
    physical KV pages between engines as ONE batched host buffer per
    dtype (int8 scale rows travel free), so a preempted or
    engine-lost request resumes elsewhere with ZERO re-prefill;
    ``role="prefill"|"decode"|"mixed"`` labels this engine for the
    router's disaggregated dispatch (fresh prompts → prefill
    specialists, whose finished pages migrate to decode specialists);
    ``host_tier_bytes=N`` stacks a bounded host-RAM spill tier on the
    prefix cache — evicted-but-hot prefix pages spill to host instead
    of dying and restore on a later hit with one batched inject.
    """

    def __init__(self, model, max_batch_size: int = 8,
                 num_blocks: int = 256, block_size: int = 16,
                 max_seq_len: Optional[int] = None,
                 use_pallas: Optional[bool] = None,
                 lazy_alloc: bool = False,
                 prefill_chunk_size: Optional[int] = None,
                 enable_prefix_cache: bool = False,
                 mixed_step: bool = True,
                 token_budgets="auto",
                 mesh=None, sharding=None,
                 kv_dtype: Optional[str] = None,
                 weight_quant: Optional[str] = None,
                 quant_collectives: bool = False,
                 sampling: bool = False,
                 draft_model=None, spec_k: int = 2,
                 engine_id: Optional[int] = None,
                 tracer=None,
                 role: str = "mixed",
                 host_tier_bytes: int = 0):
        from ..jit.serving_step import MixedStep
        # not an option: the fused mixed step is the one serving path.
        # The keyword survives only because benchmark/harness/program.py
        # passes ``mixed_step=True`` literally (ROADMAP D1: the next
        # benchmark PR drops it there, then this parameter goes)
        if not mixed_step:
            raise ValueError(
                "mixed_step=False: the split prefill/decode path and "
                "the eager dense prefill were removed in PR 29; the "
                "fused mixed step is the engine's one serving path")
        self.model = model
        # disaggregated serving (round 19): a router routes fresh
        # prompts to "prefill" specialists (big token budgets, chunked)
        # and migrates their finished pages to "decode" specialists
        # (high slot counts, int8 KV); "mixed" engines take anything —
        # the default, so single-engine users never see role policy
        if role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                "ContinuousBatchingEngine role must be 'prefill', "
                "'decode' or 'mixed'; got %r" % (role,))
        self.role = role
        # identity for multi-engine deployments (the ServingRouter's
        # health gauge + the /healthz payload key on it); defaults to a
        # process-wide sequence so standalone engines need no plumbing
        self.engine_id = int(next(_ENGINE_IDS) if engine_id is None
                             else engine_id)
        # ---- sampling / speculative validation (construction-time) --
        self.sampling = bool(sampling)
        if draft_model is not None:
            if mesh is not None or sharding is not None:
                raise ValueError(
                    "speculative decoding is single-chip for now: the "
                    "draft engine runs unsharded, so a tensor-parallel "
                    "target would mix placements — drop mesh/sharding "
                    "or drop draft_model")
            if int(spec_k) < 1:
                raise ValueError(
                    "spec_k must be >= 1 (the draft proposes at least "
                    "one token per round); got %r" % (spec_k,))
            if draft_model.config.vocab_size != model.config.vocab_size:
                raise ValueError(
                    "draft and target models must share one vocabulary "
                    "(%d vs %d): accept/reject compares token ids"
                    % (draft_model.config.vocab_size,
                       model.config.vocab_size))
        self.draft_model = draft_model
        self.spec_k = int(spec_k) if draft_model is not None else 0
        # ---- quantization validation (construction-time, PR-7 norm:
        # a clear error HERE, never a dtype/shape failure deep inside
        # tracing) --------------------------------------------------
        if kv_dtype not in (None, "float32", "bfloat16", "int8"):
            raise ValueError(
                "ContinuousBatchingEngine kv_dtype must be None (follow "
                "the model dtype), 'float32', 'bfloat16' or 'int8'; got "
                "%r" % (kv_dtype,))
        if weight_quant not in (None, "int8"):
            raise ValueError(
                "ContinuousBatchingEngine weight_quant must be None or "
                "'int8'; got %r" % (weight_quant,))
        if quant_collectives and mesh is None and sharding is None:
            raise ValueError(
                "quant_collectives=True quantizes the tensor-parallel "
                "logits all-gather; a single-chip engine has no "
                "collectives — pass mesh= (tp >= 2) or drop the flag")
        # ---- tensor-parallel serving (multi-chip) --------------------
        # mesh + ShardingConfig(axis='tp') shard the fused steps over
        # the tp axis (jit/spmd.py is the single source of the mesh /
        # per-weight-family spec logic, shared with TrainStep — pass a
        # co-located train mesh and its 'tp' axis resolves).  Head
        # divisibility and pool shape are validated HERE, not as a
        # shard_map shape failure deep in tracing.
        if mesh is not None or sharding is not None:
            from ..jit.spmd import tp_serving_context
            self.tp = tp_serving_context(model, mesh, sharding)
        else:
            self.tp = None
        self.tp_degree = self.tp.degree if self.tp is not None else 1
        self.fsdp_degree = self.tp.fsdp_degree \
            if self.tp is not None else 1
        self.cp_degree = self.tp.cp_degree if self.tp is not None else 1
        self.ep_degree = self.tp.ep_degree if self.tp is not None else 1
        # ---- context-parallel serving (round 22) --------------------
        # a 'cp' mesh axis stripes every pool's slot dim: validated
        # HERE with actionable messages (block_size divisibility, no
        # int8 pools, no spec-decode), never as a shard_map shape
        # failure deep in tracing
        if self.cp_degree > 1:
            from ..jit.spmd import validate_cp_serving
            validate_cp_serving(
                self.cp_degree, block_size,
                quantized_kv=(kv_dtype == "int8"),
                spec_decode=draft_model is not None)
        # ---- expert-parallel MoE serving (round 24) -----------------
        # an 'ep' mesh axis shards the expert banks' E dim: validated
        # HERE with actionable messages (expert-count divisibility, no
        # spec-decode), never as a shard_map shape failure; the token
        # budgets are re-checked after they resolve below (every budget
        # must stripe evenly over ep)
        if self.ep_degree > 1:
            from ..jit.spmd import validate_ep_serving
            validate_ep_serving(
                getattr(model.config, "num_local_experts", 0),
                self.ep_degree,
                spec_decode=draft_model is not None)
        if quant_collectives and self.tp is None:
            raise ValueError(
                "quant_collectives=True but the mesh's tp axis "
                "degenerates to 1 chip — there is no logits all-gather "
                "to quantize; use tp >= 2 or drop the flag")
        # lazy_alloc: pages are allocated as a sequence actually grows
        # instead of reserving the full prompt+budget footprint at
        # admission — higher occupancy for the same pool, at the cost
        # that the pool CAN run dry mid-decode.  When it does, the
        # victim request is finished early with ``truncated=True``
        # (robustness contract: step() never raises out of a full
        # batch; the other slots keep decoding).
        self.lazy_alloc = bool(lazy_alloc)
        cfg = model.config
        self.cfg = cfg
        # MoE dispatch accounting (round 24): every real token in a
        # mixed pack is routed to top_k experts in each MoE layer —
        # static per pack, counted host-side next to the collectives
        self._moe_layers = (cfg.num_hidden_layers
                            - int(getattr(cfg, "first_k_dense_replace", 0))
                            if getattr(cfg, "num_local_experts", 0)
                            else 0)
        self._moe_topk = int(getattr(cfg, "num_experts_per_tok", 0))
        self.max_batch_size = max_batch_size
        self.block_size = block_size
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        self.kv_quant = kv_dtype == "int8"
        # a page's geometry comes from the layers' ATTENTION: a latent
        # (MLA) model caches one row a token, read by every head, in
        # one pool a layer.  What moves, shards or converts K/V pages
        # and is not taught that row refuses here, each with its name.
        from ..jit.serving_step import _inner_model, latent_attention
        self.latent = latent_attention(model)
        if self.latent is not None:
            for on, what in (
                    (kv_dtype == "int8", "kv_dtype='int8' (its scales "
                     "are per kv head)"),
                    (self.tp is not None, "a mesh (tp/cp/ep shard "
                     "pools over kv heads or slots)"),
                    (draft_model is not None, "a draft model"),
                    (role != "mixed", "role=%r (page migration moves "
                     "K and V pages)" % (role,)),
                    (enable_prefix_cache, "enable_prefix_cache "
                     "(copy-on-write and the host tier copy K and V "
                     "pages)")):
                if on:
                    raise ValueError(
                        "ContinuousBatchingEngine: %s is not taught "
                        "the latent (MLA) cache row of %s"
                        % (what, type(model).__name__))
        latent_width = self.latent.latent_row \
            if self.latent is not None else None
        self.caches = [
            PagedKVCache(num_blocks, block_size,
                         cfg.num_key_value_heads, self.head_dim, dtype,
                         sink_block=True, kv_dtype=kv_dtype,
                         latent_width=getattr(
                             getattr(layer, "self_attn", None),
                             "latent_row", None))
            for layer in _inner_model(model).layers]
        # per-channel absmax PTQ: quantize ONCE at construction; every
        # step consumes the same int8+scales tree via dequant-on-use
        if weight_quant == "int8":
            from ..quantization.functional import quantize_param_tree
            self.weight_qtree = quantize_param_tree(
                {k: t._value for k, t in model.state_dict().items()})
        else:
            self.weight_qtree = None
        self.quant_collectives = bool(quant_collectives)
        if self.tp is not None:
            # re-check against the pool actually built (paranoia for
            # subclasses that override cache construction), then place:
            # each chip holds only its kv-head slice of every page
            from ..jit.spmd import validate_tp_serving
            validate_tp_serving(cfg, self.tp_degree,
                                pool_kv_heads=self.caches[0].num_kv_heads)
            pool_sh = self.tp.pool_sharding()
            scale_sh = self.tp.kv_scale_sharding() if self.kv_quant \
                else None
            for c in self.caches:
                c.place(pool_sh, scale_sh)
        if max_seq_len is None:
            max_seq_len = max(block_size,
                              num_blocks * block_size // max_batch_size)
        self.max_seq_len = max_seq_len
        self.bt_width = -(-max_seq_len // block_size)
        self._sink = self.caches[0].sink
        self.slots: List[Optional[GenerationRequest]] = \
            [None] * max_batch_size
        self.waiting: List[GenerationRequest] = []
        self.finished: Dict[int, GenerationRequest] = {}
        self._next_id = 0
        # each slot's pending token: the one its next decode span feeds
        self._tokens = np.zeros((max_batch_size,), np.int32)

        # ---- the fused mixed prefill+decode step ---------------------
        # (Ragged Paged Attention): ONE compiled module per total-token
        # budget advances decode slots AND prefill chunks together —
        # no per-chunk engine round, no prefill/decode module split
        self.chunk_size = int(prefill_chunk_size
                              or self._auto_chunk(self.max_seq_len))
        # a speculative all-decode pack is slots x (k+1) verify
        # tokens, not slots x 1 — size the budget base to it
        base_spans = max_batch_size * (self.spec_k + 1)
        if token_budgets == "auto":
            budgets = self._auto_budgets_mixed(base_spans,
                                               self.chunk_size)
        else:
            budgets = tuple(sorted({int(b) for b in token_budgets}))
            if not budgets or budgets[-1] < base_spans:
                raise ValueError(
                    "top token budget %r < %d (max_batch_size x "
                    "(spec_k+1)): an all-decode step would not fit"
                    % (token_budgets, base_spans))
        self.token_budgets = budgets
        if self.ep_degree > 1:
            from ..jit.spmd import validate_ep_serving
            validate_ep_serving(
                getattr(cfg, "num_local_experts", 0),
                self.ep_degree, budgets=budgets)
        self.mixed = MixedStep(model, self.caches, self.bt_width,
                               max_spans=max_batch_size,
                               use_pallas=use_pallas, tp=self.tp,
                               weight_qparams=self.weight_qtree,
                               quant_collectives=self.quant_collectives,
                               sampling=self.sampling,
                               spec_k=self.spec_k)
        # padding tokens spread over the sink page's slots
        self._dest_pad = (np.arange(budgets[-1], dtype=np.int32)
                          % block_size)
        # ---- speculative draft engine --------------------------------
        # the draft model's OWN per-layer pools, addressed by the SAME
        # page ids as the target's (caches[0] stays the one free-list /
        # refcount authority) — prefix sharing, COW and release carry
        # the draft KV for free.  The draft runs as a MixedStep too:
        # catch-up spans are ragged (1-2 tokens) and prefill chunks
        # mirror straight into the draft pool.
        if draft_model is not None:
            dcfg = draft_model.config
            d_dtype = jnp.bfloat16 if dcfg.dtype == "bfloat16" \
                else jnp.float32
            self.draft_caches = [
                PagedKVCache(num_blocks, block_size,
                             dcfg.num_key_value_heads,
                             dcfg.hidden_size // dcfg.num_attention_heads,
                             d_dtype, sink_block=True)
                for _ in range(dcfg.num_hidden_layers)]
            self.draft_step = MixedStep(
                draft_model, self.draft_caches, self.bt_width,
                max_spans=max_batch_size,
                use_pallas=use_pallas, sampling=self.sampling,
                return_probs=self.sampling)
            # draft packs are SMALL (proposal launches carry one token
            # per slot, catch-up at most two) — give the draft set
            # tight small bases so a 1-token-per-slot launch never pads
            # to the verify-sized budget, and carry the target's set on
            # top so chunk mirrors always fit.  Both modules' compiles
            # stay bounded by their (static) budget-set sizes.
            small = []
            b = 1
            while b < max(1, max_batch_size):
                b *= 2
            small.append(b)
            small.append(b * 2)                  # catch-up: <= 2 tokens
            self.draft_budgets = tuple(sorted(
                set(small) | set(self.token_budgets)))
            self._zero_q = (jnp.zeros((max_batch_size, cfg.vocab_size),
                                      jnp.float32)
                            if self.sampling else None)
        else:
            self.draft_caches = []
            self.draft_step = None
            self.draft_budgets = None
            self._zero_q = None
        # ---- host-RAM prefix spill tier (round 19) -------------------
        if host_tier_bytes and not enable_prefix_cache:
            raise ValueError(
                "host_tier_bytes is the prefix cache's spill tier: "
                "pass enable_prefix_cache=True (there is nothing to "
                "spill without a prefix table)")
        if host_tier_bytes and self.tp is not None:
            raise ValueError(
                "the host spill tier is single-chip for now: a "
                "tensor-parallel engine's pools are head-sharded and "
                "the batched extract/inject path moves whole pages — "
                "drop host_tier_bytes or drop mesh/sharding")
        if host_tier_bytes and draft_model is not None:
            raise ValueError(
                "a speculative engine cannot spill/restore prefix "
                "pages: a restored page carries only target-model KV, "
                "and the draft pool (addressed by the same page ids) "
                "cannot be reconstructed from it — drop "
                "host_tier_bytes or drop draft_model")
        if enable_prefix_cache:
            from .prefix_cache import HostPageTier, PrefixPageCache
            self.host_tier = (HostPageTier(int(host_tier_bytes))
                              if host_tier_bytes else None)
            self.prefix_cache = PrefixPageCache(
                self.caches[0], block_size, all_caches=self.caches,
                host_tier=self.host_tier)
        else:
            self.host_tier = None
            self.prefix_cache = None
        # published-so-far snapshot of the prefix cache's host-side
        # stat counters (evictions by outcome, spills/hits/restores);
        # _sync_prefix_stats diffs against it so the process-wide
        # metric counters see each increment exactly once
        self._pc_published: Dict[str, int] = {}
        self._chunk_rr = 0           # round-robin cursor over chunk work

        from ..observability import default_registry
        from ..observability.request_trace import resolve_tracer
        # bounded per-request phase tracer (round 16): typed spans for
        # admission, per-chunk prefill, sampled decode steps, first
        # token, preempt and finish — host-side appends only, keyed by
        # this engine's req_ids (a router merges them fleet-wide via
        # fleet_trace).  Default ON; tracer=False is the no-op stub.
        self.tracer = resolve_tracer(tracer)
        # decode spans are SAMPLED (every Nth step per request) so a
        # long generation neither floods the trace nor hits the
        # per-request event cap
        self.trace_decode_every = 8
        r = default_registry()
        self._m_queue = r.gauge(
            "serving_queue_depth", "requests waiting for a free slot")
        self._m_occupancy = r.gauge(
            "serving_slot_occupancy_ratio",
            "running slots / max_batch_size")
        self._m_kv_util = r.gauge(
            "serving_kv_page_utilization_ratio",
            "allocated KV pages / pool size")
        self._m_prefill = r.histogram(
            "serving_prefill_duration_seconds",
            "one warm fused step whose pack advanced a prefill chunk")
        self._m_decode = r.histogram(
            "serving_decode_step_duration_seconds",
            "one warm fused step whose pack advanced a decode span")
        self._m_ttft = r.histogram(
            "serving_ttft_seconds", "admission wait + prefill to first "
            "token (time-to-first-token)")
        self._m_tpot = r.histogram(
            "serving_tpot_seconds",
            "mean per-token decode latency after the first token",
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                     0.25, 0.5, 1.0))
        self._m_requests = r.counter(
            "serving_requests_total", "finished generation requests",
            labels=("outcome",))
        self._m_tokens = r.counter(
            "serving_tokens_total", "tokens generated")
        self._m_truncated = r.counter(
            "serving_truncated_victims_total",
            "requests finished early because the KV pool ran dry "
            "(lazy_alloc victim contract)")
        self._m_prefix_lookups = r.counter(
            "serving_prefix_cache_lookups_total",
            "prompt admissions checked against the prefix table",
            labels=("outcome",))
        self._m_prefix_hit_tokens = r.counter(
            "serving_prefix_cache_hit_tokens_total",
            "prompt tokens served from shared prefix pages instead of "
            "recompute")
        self._m_prefix_evictions = r.counter(
            "serving_prefix_cache_evictions_total",
            "prefix table entries visited by eviction under pool "
            "pressure, by outcome (reclaimed = page returned to the "
            "free list, spilled first when a host tier is attached; "
            "skipped_pinned = a live request still holds the page, so "
            "the entry was passed over — sustained skips explain "
            "cache-pressure stalls)", labels=("outcome",))
        self._m_evict_reclaimed = \
            self._m_prefix_evictions.labels(outcome="reclaimed")
        self._m_evict_skipped = \
            self._m_prefix_evictions.labels(outcome="skipped_pinned")
        self._m_migrations = r.counter(
            "serving_page_migrations_total",
            "KV page-set migrations through this engine, by direction "
            "(out = extract_request serialized a sequence's pages to "
            "host; in = inject_request scattered a migrated buffer "
            "into this pool)", labels=("direction",))
        self._m_migrations_out = \
            self._m_migrations.labels(direction="out")
        self._m_migrations_in = self._m_migrations.labels(direction="in")
        self._m_migrated_bytes = r.counter(
            "serving_migrated_bytes_total",
            "payload bytes moved across the host link by page "
            "migration (each migration counts its buffer once on "
            "extract — device-to-host — and once on inject — "
            "host-to-device)")
        self._m_host_spills = r.counter(
            "serving_host_tier_spills_total",
            "evicted prefix pages serialized into the host-RAM spill "
            "tier instead of dying")
        self._m_host_hits = r.counter(
            "serving_host_tier_hits_total",
            "prefix lookups whose chain continued into the host tier "
            "(spilled pages found for the prompt)")
        self._m_host_restores = r.counter(
            "serving_host_tier_restores_total",
            "spilled pages injected back into the device pool and "
            "re-registered under their digest keys")
        self._m_chunk_queue = r.gauge(
            "serving_prefill_chunk_queue_depth",
            "prefill chunks still pending across admitted requests")
        self._m_mixed_compiles = r.counter(
            "serving_mixed_step_compiles_total",
            "fused MixedStep traces (bounded by the token-budget-set "
            "size)")
        self._m_mixed_span_tokens = r.counter(
            "serving_mixed_span_tokens_total",
            "tokens advanced by the fused mixed step, by span kind",
            labels=("kind",))
        # resolve the labeled children ONCE: .labels() is a lock + dict
        # probe, and the mixed step pays it every engine round
        self._m_mixed_tok_decode = \
            self._m_mixed_span_tokens.labels(kind="decode")
        self._m_mixed_tok_prefill = \
            self._m_mixed_span_tokens.labels(kind="prefill")
        self._m_tp_degree = r.gauge(
            "serving_tp_degree",
            "tensor-parallel degree of the most recently constructed "
            "engine in this process (1 = single chip)")
        self._m_tp_degree.set(self.tp_degree)
        self._m_tp_collective = r.counter(
            "serving_tp_collective_bytes_total",
            "per-chip activation bytes moved through the sharded "
            "step's collectives (psum per layer boundary, exact "
            "embedding psum, exact logits all-gather)", labels=("op",))
        self._m_tp_psum = self._m_tp_collective.labels(op="psum")
        self._m_tp_all_gather = \
            self._m_tp_collective.labels(op="all_gather")
        # 2D serving mesh (round 21): per-axis shape of the most
        # recently constructed engine's mesh — fsdp (weight storage),
        # tp (compute), dp (replica); 1 = the axis is absent
        self._m_mesh_shape = r.gauge(
            "serving_mesh_shape",
            "serving mesh degree per axis for the most recently "
            "constructed engine (fsdp = weight-storage sharding, tp = "
            "tensor parallel, dp = replica) — 1 means the axis is "
            "absent", labels=("axis",))
        mesh_sizes = dict(self.tp.mesh.shape) if self.tp is not None \
            else {}
        self._m_mesh_shape.labels(axis="fsdp").set(
            self.fsdp_degree)
        self._m_mesh_shape.labels(axis="tp").set(self.tp_degree)
        self._m_mesh_shape.labels(axis="dp").set(
            int(mesh_sizes.get("dp", 1)))
        self._m_mesh_shape.labels(axis="cp").set(self.cp_degree)
        self._m_mesh_shape.labels(axis="ep").set(self.ep_degree)
        # context-parallel serving (round 22): pool-stripe degree and
        # the stripe-merge collective payload
        self._m_cp_degree = r.gauge(
            "serving_cp_degree",
            "context-parallel degree of the most recently constructed "
            "engine (cp stripes every KV pool's slot dim — per-chip "
            "pool HBM is 1/cp; 1 = pools not striped)")
        self._m_cp_degree.set(self.cp_degree)
        self._m_cp_collective = r.counter(
            "serving_cp_collective_bytes_total",
            "per-chip bytes received by the cross-chip online-softmax "
            "stripe merge (one all_gather of the (o, m, l) partial "
            "rows per layer per sharded dispatch)", labels=("op",))
        self._m_cp_all_gather = \
            self._m_cp_collective.labels(op="all_gather")
        # expert-parallel MoE serving (round 24): expert-bank shard
        # degree and the dispatch/combine payloads of the fused step
        self._m_ep_degree = r.gauge(
            "serving_ep_degree",
            "expert-parallel degree of the most recently constructed "
            "engine (ep shards every MoE expert bank's E dim — "
            "per-chip expert HBM is 1/ep; 1 = expert banks replicated)")
        self._m_ep_degree.set(self.ep_degree)
        self._m_moe_dispatch = r.counter(
            "serving_moe_dispatch_tokens_total",
            "token->expert assignments made by the fused MoE serving "
            "dispatch (tokens x top_k x MoE layers), by fate — the "
            "dispatch is DROPLESS (capacity == worst-case load), so "
            "'dropped' stays 0 by construction and a nonzero value "
            "means the capacity invariant broke", labels=("fate",))
        self._m_moe_routed = self._m_moe_dispatch.labels(fate="routed")
        # resolve the 'dropped' child eagerly so /metrics always shows
        # the 0 that documents droplessness
        self._m_moe_dropped = self._m_moe_dispatch.labels(fate="dropped")
        # a step with the sorted grouped product (every one-chip MoE):
        # the rows each expert HELD here was given (the step's own
        # count, fetched with the tokens); where the bank holds a share
        # of its router's experts the rest of ``routed`` landed elsewhere
        self._m_moe_expert_load = r.counter(
            "serving_moe_expert_load_total",
            "token->expert assignments that landed on each expert this "
            "engine holds (index within the held bank), summed over "
            "the MoE layers", labels=("expert",))
        self._m_moe_expert = [
            self._m_moe_expert_load.labels(expert=str(e))
            for e in range(max(0, self.mixed.n_stats - 3))]
        self._m_latent_row = r.gauge(
            "serving_kv_latent_row_bytes",
            "bytes one token's cached latent row takes in one layer's "
            "pool of the most recently constructed engine, padding to "
            "whole lanes included (0 = K and V pools, no latent row)")
        self._m_latent_row.set(
            latent_width * self.caches[0].key_cache.dtype.itemsize
            if latent_width else 0)
        self._m_ep_collective = r.counter(
            "serving_ep_collective_bytes_total",
            "per-chip bytes moved by the expert-parallel dispatch "
            "(all_to_all = the send/return buffer pair per MoE layer, "
            "all_gather = re-replicating the combined token stripes)",
            labels=("op",))
        self._m_ep_all_to_all = \
            self._m_ep_collective.labels(op="all_to_all")
        self._m_ep_all_gather = \
            self._m_ep_collective.labels(op="all_gather")
        self._m_fsdp_gather = r.counter(
            "spmd_allgather_bytes_total",
            "per-chip bytes received by spmd param all-gathers, by "
            "site: the 2D train step's per-step param gather "
            "(train_params) and the sharded serving prologue's fsdp "
            "gather (serving_params)", labels=("site",)
        ).labels(site="serving_params")
        # static per-dispatch payload of the prologue's fsdp param
        # gather (0 without an fsdp axis) — counted per sharded
        # dispatch next to the activation collectives
        if self.tp is not None:
            tree = self.weight_qtree if self.weight_qtree is not None \
                else {k: t._value for k, t in model.state_dict().items()}
            self._fsdp_gather_bytes = self.tp.fsdp_gather_bytes(tree)
        else:
            self._fsdp_gather_bytes = 0
        self._m_kv_quant_dtype = r.gauge(
            "serving_kv_quant_dtype",
            "KV-cache element width in bits of the most recently "
            "constructed engine (8 = int8 quantized pools, 16/32 = fp)")
        # read the CONSTRUCTED pool's dtype (kv_dtype may explicitly
        # override the model dtype, e.g. bfloat16 pools under fp32)
        self._m_kv_quant_dtype.set(
            self.caches[0].key_cache.dtype.itemsize * 8)
        self._m_quant_collective = r.counter(
            "serving_quant_collective_bytes_total",
            "per-chip bytes moved through QUANTIZED collectives (the "
            "EQuARX-style int8 logits all-gather: codes + per-shard "
            "scales)", labels=("op",))
        self._m_quant_all_gather = \
            self._m_quant_collective.labels(op="all_gather")
        self._m_quant_mismatch = r.counter(
            "serving_quant_token_mismatch_total",
            "greedy tokens that diverged from the fp32 reference "
            "engine on a paired run (published by the quantization "
            "bench/tests via record_token_mismatches — the tolerance "
            "gate's numerator)")
        self._m_sampling_mode = r.gauge(
            "serving_sampling_mode",
            "1 = the stochastic sampling epilogue is compiled into "
            "this process's most recently constructed engine, 0 = "
            "greedy-only")
        self._m_sampling_mode.set(1 if self.sampling else 0)
        self._m_spec_proposed = r.counter(
            "serving_spec_proposed_tokens_total",
            "draft tokens proposed to the speculative verifier")
        self._m_spec_accepted = r.counter(
            "serving_spec_accepted_tokens_total",
            "proposed draft tokens the target verifier accepted "
            "(acceptance rate = accepted / proposed)")
        self._m_draft_step = r.histogram(
            "serving_spec_draft_step_duration_seconds",
            "one fused draft-model launch (catch-up + proposal or "
            "chunk mirror; compile warmup excluded)",
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                     0.25, 0.5, 1.0))
        # the step record (see step()): the index of the step that is
        # running or, between steps, of the next one; what the running
        # step has noted so far (None between steps); the request ids
        # admitted since the last record; the open phase span
        self._step_no = 0
        self._rec: Optional[Dict] = None
        self._admitted: List[int] = []
        self._phase_ann = None
        # per-ENGINE cumulative host counters (round 20): the
        # process-wide prometheus counters aggregate across every
        # engine in the process, so the capacity plane's per-engine
        # windowed rates read THESE off health_payload instead
        self.counters: Dict[str, int] = {
            "tokens_generated": 0, "requests_received": 0,
            "requests_admitted": 0, "preempts": 0}
        # lazily computed serving-step cost_analysis block (round 20
        # capacity plane); stays None until efficiency_stats(
        # compute=True) runs — a health scrape must never compile —
        # and a FAILED probe latches too (one compile attempt ever)
        self._efficiency_stats: Optional[Dict] = None
        self._efficiency_failed = False

    @staticmethod
    def _auto_chunk(max_seq_len: int) -> int:
        """The default prefill chunk: the pow2 ceil of max_seq_len,
        capped at 512 (longer prompts prefill in chunks of it)."""
        top = 1
        while top < max_seq_len:
            top *= 2
        return min(top, 512)

    @staticmethod
    def _auto_budgets_mixed(slots: int, chunk: int):
        """Geometric total-token budgets for the mixed step: from the
        pow2 ceil of the slot count (the all-decode pack) doubling up
        past slots + chunk (every slot decoding while a full prefill
        chunk rides along)."""
        b = 1
        while b < max(1, slots):
            b *= 2
        out = [b]
        while b < slots + chunk:
            b *= 2
            out.append(b)
        return tuple(out)

    # ---- public API ----------------------------------------------------
    def add_request(self, prompt_ids, max_new_tokens=16,
                    eos_token_id=None, temperature: float = 0.0,
                    top_k: int = 0, top_p: float = 0.0, seed: int = 0,
                    n: int = 1):
        """Queue one prompt.  ``temperature``/``top_k``/``top_p``/
        ``seed`` select stochastic sampling (engine must be built with
        ``sampling=True``; temperature 0 = greedy).  ``n>1`` queues n
        generations of the SAME prompt that share one prefilled prefix
        through the copy-on-write prefix-page machinery (requires
        ``enable_prefix_cache=True``): generation i samples with
        ``seed + i``, children admit only after the first generation's
        prefill publishes the shared pages (ref++ on every shared
        page, per-generation divergent suffixes).  Returns the req_id,
        or the list of n req_ids when ``n > 1``."""
        if (temperature or top_k or top_p or seed) and not self.sampling:
            raise ValueError(
                "per-request sampling parameters need a sampling "
                "engine: construct ContinuousBatchingEngine("
                "sampling=True, ...) — the greedy engine's compiled "
                "steps have no sampling epilogue")
        if n < 1:
            raise ValueError("add_request n must be >= 1, got %r" % n)
        if n > 1 and self.prefix_cache is None:
            raise ValueError(
                "add_request(n=%d) shares one prefilled prefix across "
                "generations via the prefix-page cache: construct the "
                "engine with enable_prefix_cache=True" % n)
        prompt = np.asarray(prompt_ids, np.int64).reshape(-1)
        need = self.caches[0].blocks_needed(len(prompt) + max_new_tokens)
        if need > self.bt_width:
            raise ValueError(
                "request needs %d pages but the engine's block-table "
                "width is %d (max_seq_len=%d); raise max_seq_len"
                % (need, self.bt_width, self.max_seq_len))
        min_need = need if not self.lazy_alloc else \
            self.caches[0].blocks_needed(len(prompt) + 1)
        if min_need > self.caches[0].num_blocks:
            # would never admit: _admit waits for pages that can't exist
            # (lazy mode only needs the prompt to fit — the tail may be
            # truncated if the pool runs dry)
            raise ValueError(
                "request needs %d pages but the pool only has %d; "
                "raise num_blocks" % (min_need, self.caches[0].num_blocks))
        ids = []
        parent = None
        for i in range(n):
            req = GenerationRequest(
                req_id=self._next_id, prompt_ids=prompt,
                max_new_tokens=max_new_tokens, eos_token_id=eos_token_id,
                temperature=float(temperature), top_k=int(top_k),
                top_p=float(top_p), seed=int(seed) + i,
                parent_req=parent)
            if parent is None:
                parent = req
            self._next_id += 1
            req.t_submit = time.perf_counter()
            self.waiting.append(req)
            ids.append(req.req_id)
        self.counters["requests_received"] += n
        self._m_queue.set(len(self.waiting))
        return ids[0] if n == 1 else ids

    def has_work(self) -> bool:
        return bool(self.waiting) or any(s is not None
                                         for s in self.slots)

    def step(self) -> List[int]:
        """Admit waiting requests, then advance the engine one round:
        every running slot's decode token AND as many pending prefill
        chunks as the token budget holds, packed into one fused launch.
        Returns the req_ids finished this step (a one-token budget or
        EOS on the first sampled token ends a request in the step that
        prefilled it, and a lazy-alloc victim is truncated before the
        launch; multi-engine callers key on the returned ids).

        The step records itself.  Under a profiler it is the span
        ``engine.step`` (``step_num`` = its index) holding six
        consecutive phase spans ``engine.admit | pack | fill | dispatch
        | fetch | book`` on the device trace's own clock; and, unless
        the engine was built with ``tracer=False``, it ends by writing
        ONE ``serving.step`` record (``_write_step_record``) whose
        ``step`` is the same index: the join between the two clocks."""
        t0 = time.perf_counter()
        self._rec = {"budget": 0, "n_dec": 0, "n_pre": 0, "spans": [],
                     "compiled": False}
        try:
            with jax.profiler.StepTraceAnnotation(
                    "engine.step", step_num=self._step_no):
                self._phase("engine.admit")
                self._admit()
                self._rec["t_admit"] = self._phase("engine.pack")
                done = self._run_mixed_step()
                running = sum(s is not None for s in self.slots)
                self._m_queue.set(len(self.waiting))
                self._m_occupancy.set(
                    running / max(1, self.max_batch_size))
                cache = self.caches[0]
                self._m_kv_util.set(
                    1.0 - len(cache._free) / max(1, cache.num_blocks))
                # chunks consume no engine round of their own, but the
                # backlog gauge still reports what is pending
                self._m_chunk_queue.set(self._pending_chunks())
                self._sync_prefix_stats()
                t_end = self._phase(None)
            if self.tracer.enabled:
                self._write_step_record(t0, t_end, running)
        finally:
            # restore the documented outside-a-step invariants even on
            # a raising step
            self._phase(None)
            self._rec = None
            self._admitted = []
            self._step_no += 1
        return done

    def _phase(self, name: Optional[str]) -> float:
        """Close the step's open phase span and open ``name`` (``None``
        opens none: the launch's two phases are ``MixedStep``'s own).
        A step's phases are consecutive, so one instant ends one and
        starts the next; returns that instant."""
        if self._phase_ann is not None:
            self._phase_ann.__exit__(None, None, None)
            self._phase_ann = None
        if name is not None:
            self._phase_ann = jax.profiler.TraceAnnotation(name)
            self._phase_ann.__enter__()
        return time.perf_counter()

    def _write_step_record(self, t0: float, t_end: float, running: int):
        """The step's ONE record: ``span_log.record("serving.step", t0,
        t_end, cat="serving", ...)``, all clocks ``time.perf_counter``.

        - ``engine``, ``step``: this engine's id in the process; the
          step's index from 0, counted by the engine (= the
          ``step_num`` of the profiler span ``engine.step``).
        - ``t_admit``, ``t_pack``, ``t_fill``, ``t_dispatch``,
          ``t_tokens``: phase boundaries.  ``_admit`` done; pages
          grown, spans chosen and their tokens listed; the pack filled;
          the jitted call has RETURNED (work enqueued); the sampled
          token ids are on the host, which is where the wait for the
          device ends and the instant at which every token this step
          emits (``spans[:, 0]``) reached the host.  ``t_end`` closes
          the bookkeeping.  A step that launched nothing has its
          missing boundaries at the next one it has.
        - ``budget``, ``tokens``, ``n_dec``, ``n_pre``: the padded
          token budget launched (0: nothing launched), the real tokens
          in the pack, its decode spans, its prefill tokens.
        - ``spans``: int32 ``[n, 3]`` rows ``(req_id, q_len, kv_len)``
          in pack order, as packed.  A prefix-cache hit is not in it:
          it was never computed.
        - ``attn_rows``: the q rows per kv head that the mixed launch's
          attention computes in each layer (``MixedStep.attn_rows``):
          beside ``tokens`` x the GQA group size it says how much of
          the launch is real.  For a latent model the rows are tokens
          x heads.
        - ``attn_blocks``, ``attn_blocks_masked``: for a latent model
          on the Pallas launch, the key blocks one layer's launch walks
          for the step's spans and how many of them took the masked
          body (``MixedStep.attn_blocks``: host integers from the
          spans' ``(q_len, kv_len)``); 0 otherwise.
        - ``moe_rows``, ``moe_rows_top``: the assignments that landed
          on the experts this engine holds, summed over the routed
          layers, and the fullest held expert's; counted by the step
          (real tokens only, the pack's padding nowhere) and fetched
          with the token ids.  A bank that holds every expert of its
          router reads ``tokens`` x ``top_k`` x layers, a share of
          them its share.  0 for a dense model, and under an ``ep``
          mesh, whose step does not count.
        - ``moe_tiles``: the row tiles those rows took in the grouped
          expert products, summed over the routed layers (each
          expert's rows start on a tile boundary:
          ``ceil(rows / tile)`` an expert a layer, the tile
          ``MixedStep.moe_tile_rows(budget)`` rows); ``moe_rows`` over
          ``moe_tiles`` x the tile's rows is how much of what the
          experts multiplied was a token's.  Counted by the step under
          either lowering; the Pallas launch visits exactly these.
        - ``admitted``: request ids admitted since the last record.
        - ``running``, ``waiting``: occupied slots and queue depth at
          the step's end.  ``compiled``: the launch traced a module.

        The speculative round fills the same fields with what it has:
        the draft launches count as ``pack`` and ``n_dec`` counts
        verify spans (``q_len`` k+1).

        Bound: ``span_log`` keeps its newest 16,384 entries, from every
        writer (eight minutes of 30 ms steps); ``spans`` is one array,
        so a full 64-slot record stays under 1 KB."""
        rec = self._rec
        bounds, t = {}, t_end
        for key in ("t_tokens", "t_dispatch", "t_fill", "t_pack",
                    "t_admit"):
            t = bounds[key] = rec.get(key, t)
        spans = np.asarray(rec["spans"], np.int32).reshape(-1, 3)
        blocks, masked = self.mixed.attn_blocks(spans[:, 1], spans[:, 2])
        span_log.record(
            STEP_SPAN, t0, t_end, cat="serving", engine=self.engine_id,
            step=self._step_no, **bounds, budget=rec["budget"],
            tokens=int(spans[:, 1].sum()), n_dec=rec["n_dec"],
            n_pre=rec["n_pre"], spans=spans,
            attn_rows=(self.mixed.attn_rows(rec["budget"], spans[:, 1])
                       if rec["budget"] else 0),
            attn_blocks=blocks, attn_blocks_masked=masked,
            moe_rows=rec.get("moe_rows", 0),
            moe_rows_top=rec.get("moe_rows_top", 0),
            moe_tiles=rec.get("moe_tiles", 0),
            admitted=tuple(self._admitted), running=running,
            waiting=len(self.waiting), compiled=rec["compiled"])

    def run_to_completion(self) -> Dict[int, List[int]]:
        while self.has_work():
            self.step()
        return {rid: r.output_ids for rid, r in self.finished.items()}

    def result(self, req_id: int) -> List[int]:
        return self.finished[req_id].output_ids

    def preempt_request(self, req_id: int) -> Tuple[np.ndarray, List[int]]:
        """Pull a waiting or running request OUT of the engine and
        return ``(prompt_ids, generated_ids)`` so an admission plane can
        re-admit it elsewhere (preempt-and-requeue: the request resumes
        on another engine with its generated tokens re-prefixed onto the
        prompt — NOT the lazy-alloc victim-truncation path, which ends a
        request early).

        A running slot is released through the refcounted
        ``free_sequence`` path — the ONLY release path — so pages shared
        with the prefix table or another live request survive, COW
        copies return to the pool, and an int8 pool's per-page scale
        rows stay consistent (scales live per PHYSICAL page and carry no
        per-request state).  The request is NOT finished: no outcome
        counter fires, nothing lands in ``finished``.  Raises KeyError
        when ``req_id`` is neither waiting nor on a slot (already
        finished requests are not preemptible)."""
        for i, r in enumerate(self.waiting):
            if r.req_id == req_id:
                self.waiting.pop(i)
                self._m_queue.set(len(self.waiting))
                r.state = "preempted"
                self.counters["preempts"] += 1
                self.tracer.event(req_id, "preempt", from_state="waiting",
                                  tokens=len(r.output_ids))
                return r.prompt_ids, list(r.output_ids)
        for r in self.slots:
            if r is None or r.req_id != req_id:
                continue
            self._release_slot(r)
            r.slot = -1
            r.state = "preempted"
            self.counters["preempts"] += 1
            self.tracer.event(req_id, "preempt", from_state="running",
                              tokens=len(r.output_ids))
            return r.prompt_ids, list(r.output_ids)
        raise KeyError(
            "preempt_request(%r): request is neither waiting nor "
            "running on this engine" % (req_id,))

    # ---- KV page migration (round 19) -----------------------------------
    def migration_geometry(self):
        """The pool geometry ``(layers, block_size, kv_heads, head_dim,
        kv_dtype)`` page buffers extracted from / injected into this
        engine must match — or None when this engine cannot migrate
        pages at all (tensor-parallel pools are head-sharded; a
        speculative engine's draft KV cannot travel).  Admission planes
        pre-check this so they never extract a buffer no target can
        take (a failed migration degrades to paying the prefill
        twice)."""
        if self.tp is not None or self.draft_step is not None \
                or self.latent is not None:
            return None
        return (len(self.caches),) + self.caches[0].page_geometry()

    def extract_request(self, req_id: int):
        """``preempt_request`` plus page extraction: pull the request
        out AND serialize its KV pages to one host
        :class:`~paddle_tpu.ops.paged_attention.KVPageBuffer` (one
        batched device→host copy per dtype) BEFORE the refcounted
        release, so an admission plane can resume it on another engine
        with ZERO re-prefill (``inject_request``).  Returns
        ``(prompt_ids, generated_ids, buffer)``; ``buffer`` is None
        when the request holds no resumable KV (still waiting, or
        mid-prefill) or when this engine cannot extract (tensor-
        parallel pools are head-sharded; a speculative engine's draft
        KV cannot travel) — the caller then falls back to the r15
        re-prefill resume."""
        buf = None
        if self.migration_geometry() is not None:
            for r in self.slots:
                if (r is not None and r.req_id == req_id
                        and r.state == "running" and r.seq_len > 0):
                    from ..jit.serving_step import extract_blocks
                    n_cov = self.caches[0].blocks_needed(r.seq_len)
                    buf = extract_blocks(self.caches,
                                         r.block_ids[:n_cov],
                                         n_tokens=r.seq_len)
                    break
        prompt, gen = self.preempt_request(req_id)
        if buf is not None:
            self._m_migrations_out.inc()
            self._m_migrated_bytes.inc(buf.nbytes)
        return prompt, gen, buf

    def inject_request(self, prompt_ids, buffer, max_new_tokens=16,
                       eos_token_id=None, temperature: float = 0.0,
                       top_k: int = 0, top_p: float = 0.0,
                       seed: int = 0) -> int:
        """Admit a MIGRATED request straight into a decode slot: the
        buffer's pages scatter into freshly allocated pool pages in ONE
        donated dispatch, the request starts in state "running" with
        its last prompt token pending, and the next engine step
        advances it as a plain decode span — zero re-prefill.  The
        covered full pages re-register under the same blake2b digest
        chain the prefix cache keys on, so affinity and COW sharing
        work on the target exactly as if it had prefilled the prompt
        itself.

        ``prompt_ids`` is the RESUME prompt (original prompt plus every
        token already generated); ``buffer.n_tokens`` must equal
        ``len(prompt_ids) - 1`` — the KV of everything but the last
        token, whose forward pass produces the next one.

        The buffer carries KV, NOT sampling state: a stochastic
        request must re-pass its ``temperature``/``top_k``/``top_p``/
        ``seed`` here (exactly ``add_request``'s contract — defaults
        are greedy).  The r14 counter-based PRNG keys on (seed, token
        position), so a re-seeded migrated stream samples the same
        distribution path it would have on the source engine.

        Raises ``ValueError`` for a request this engine can never hold
        (geometry/kv_dtype mismatch, block-table width) and
        ``RuntimeError`` for transient capacity (no free slot, pool
        cannot cover the pages) — both BEFORE any side effect, so the
        caller can fall back to ``add_request`` (re-prefill resume)."""
        if buffer is None:
            raise ValueError(
                "inject_request needs a KVPageBuffer — use add_request "
                "for a fresh (un-migrated) prompt")
        if self.tp is not None:
            raise ValueError(
                "page migration is single-chip for now: a tensor-"
                "parallel engine's pools are head-sharded and the "
                "batched inject moves whole pages")
        if self.latent is not None:
            raise ValueError(
                "page migration is not taught the latent (MLA) cache "
                "row: a KVPageBuffer carries K and V pages per kv head")
        if self.draft_step is not None:
            raise ValueError(
                "a speculative engine cannot accept migrated pages: "
                "the buffer carries only target-model KV and the draft "
                "pool (addressed by the same page ids) cannot be "
                "reconstructed from it")
        here = (len(self.caches),) + self.caches[0].page_geometry()
        if here != buffer.geometry():
            raise ValueError(
                "inject_request: pool geometry mismatch — buffer was "
                "extracted from (layers, block_size, kv_heads, "
                "head_dim, kv_dtype)=%r but this engine's pools are "
                "%r; KV pages only migrate between engines with "
                "identical pool geometry (including kv_dtype)"
                % (buffer.geometry(), here))
        if int(max_new_tokens) < 1:
            raise ValueError(
                "inject_request max_new_tokens must be >= 1; a "
                "migrated request with no remaining budget should "
                "complete at the router, not resume")
        if (temperature or top_k or top_p or seed) and not self.sampling:
            raise ValueError(
                "per-request sampling parameters need a sampling "
                "engine: construct ContinuousBatchingEngine("
                "sampling=True, ...) — the greedy engine's compiled "
                "steps have no sampling epilogue")
        prompt = np.asarray(prompt_ids, np.int64).reshape(-1)
        L = len(prompt)
        if buffer.n_tokens != L - 1:
            raise ValueError(
                "inject_request: buffer covers %d token(s) of KV but "
                "the resume prompt has %d — a migrated request resumes "
                "with exactly its last token pending (n_tokens == "
                "len(prompt_ids) - 1)" % (buffer.n_tokens, L))
        cache = self.caches[0]
        n_cov = cache.blocks_needed(buffer.n_tokens)
        if buffer.n_pages != n_cov:
            raise ValueError(
                "inject_request: buffer holds %d page(s) but %d cover "
                "its %d token(s) at block_size=%d"
                % (buffer.n_pages, n_cov, buffer.n_tokens,
                   self.block_size))
        total_need = cache.blocks_needed(
            L + (1 if self.lazy_alloc else int(max_new_tokens)))
        if total_need > self.bt_width:
            raise ValueError(
                "request needs %d pages but the engine's block-table "
                "width is %d (max_seq_len=%d); raise max_seq_len"
                % (total_need, self.bt_width, self.max_seq_len))
        slot = next((i for i, s in enumerate(self.slots) if s is None),
                    None)
        if slot is None:
            raise RuntimeError(
                "inject_request: no free slot — inject only into "
                "engines with slot capacity (migrated requests do not "
                "queue; their pages would pin pool pages while "
                "waiting)")
        available = len(cache._free)
        if self.prefix_cache is not None:
            available += self.prefix_cache.evictable_count()
        if total_need > available:
            raise RuntimeError(
                "inject_request: pool cannot cover %d page(s) "
                "(%d free + %d evictable)"
                % (total_need, len(cache._free), available
                   - len(cache._free)))

        # ---- commit ---------------------------------------------------
        from ..jit.serving_step import inject_blocks
        # one batched spill for the whole deficit (see _try_admit)
        short = total_need - len(cache._free)
        if short > 0 and self.prefix_cache is not None:
            self.prefix_cache.evict(short)
            self._sync_prefix_stats()
        req = GenerationRequest(
            req_id=self._next_id, prompt_ids=prompt,
            max_new_tokens=int(max_new_tokens),
            eos_token_id=eos_token_id,
            temperature=float(temperature), top_k=int(top_k),
            top_p=float(top_p), seed=int(seed))
        self._next_id += 1
        req.t_submit = time.perf_counter()
        req.block_ids = [self._alloc_block() for _ in range(total_need)]
        inject_blocks(self.caches, buffer, req.block_ids[:n_cov])
        req.slot = slot
        req.state = "running"
        req.seq_len = buffer.n_tokens
        req.prefill_pos = L
        req.prefix_hit_tokens = 0
        self.slots[slot] = req
        self._tokens[slot] = int(prompt[-1])
        if self.prefix_cache is not None:
            # re-register the COVERED full pages under the same digest
            # chain (truncate the prompt to them: pages past n_tokens
            # hold no KV yet and must not be published)
            full = (buffer.n_tokens // self.block_size) * self.block_size
            if full:
                self.prefix_cache.register(prompt[:full], req.block_ids)
        self._m_migrations_in.inc()
        self._m_migrated_bytes.inc(buffer.nbytes)
        self.counters["requests_received"] += 1
        self.counters["requests_admitted"] += 1
        self._admitted.append(req.req_id)
        self.tracer.event(req.req_id, "admit", slot=slot,
                          prefix_hit_tokens=0, prompt_tokens=L,
                          enqueue_ts=req.t_submit, migrated=True,
                          step=self._step_no)
        return req.req_id

    def health_payload(self) -> Dict[str, int]:
        """Load/health snapshot for admission planes: the same stats
        the observability gauges read (occupancy, KV-page utilization,
        chunk-queue depth), as one host-side dict — the body
        ``/healthz`` serves when this engine is installed as the
        process's health provider (``observability.set_health_provider(
        engine.health_payload)``), so a router scrapes load without
        parsing Prometheus text.

        Round 20: the payload also carries ``counters`` — this
        engine's cumulative host-side counts (tokens, admissions,
        preempts, prefix lookups/hits, host-tier spills/restores) —
        which the capacity plane's ``SignalWindow``\\ s turn into
        rolling rates and drifts, and ``efficiency`` once (and only
        once) ``efficiency_stats(compute=True)`` has run — a health
        scrape itself never triggers a compile."""
        pc = self.prefix_cache
        cache = self.caches[0]
        payload = {
            "engine_id": self.engine_id,
            "role": self.role,
            "occupancy": sum(s is not None for s in self.slots),
            "slots": self.max_batch_size,
            "waiting": len(self.waiting),
            "free_pages": len(cache._free),
            "total_pages": cache.num_blocks,
            "chunk_queue_depth": self._pending_chunks(),
            # round 20: pages the prefix cache could reclaim RIGHT NOW
            # (table entries no live request holds) — the capacity
            # plane's saturation must not read a cache-warm idle
            # engine as full (those pages free under pressure)
            "evictable_pages": (pc.evictable_count()
                                if pc is not None else 0),
            # round 19: the host spill tier's footprint rides the same
            # payload the router's load_score and the r16 SLO plane
            # already scrape — no extra endpoint
            "host_tier_bytes": (self.host_tier.bytes
                                if self.host_tier is not None else 0),
            "host_tier_entries": (len(self.host_tier)
                                  if self.host_tier is not None else 0),
        }
        payload["counters"] = {
            **self.counters,
            "prefix_lookups": (pc.hits + pc.misses) if pc is not None
            else 0,
            "prefix_hits": pc.hits if pc is not None else 0,
            "host_tier_spills": pc.spills if pc is not None else 0,
            "host_tier_restores": pc.restores if pc is not None else 0,
        }
        if self._efficiency_stats is not None:
            payload["efficiency"] = self._efficiency_stats
        return payload

    def efficiency_stats(self, compute: bool = False) -> Optional[Dict]:
        """Serving-step device-efficiency numbers off the COMPILED
        step's ``cost_analysis`` — the serving twin of the round-9
        train MFU probe, with the same contract: lazy, cached for the
        engine's lifetime, one extra AOT compile ever, opt out with
        ``PADDLE_TPU_MFU_COST_ANALYSIS=0`` (tests/conftest.py sets it,
        so the tier-1 budget never pays this).  ``compute=False`` (the
        health-payload read) returns the cached block or None — it
        NEVER compiles.

        The probed launch is the engine's steady-state decode shape:
        the SMALLEST token budget an all-decode pack fits.  Per-token
        numbers amortize over the launch's packed token capacity —
        padding spans do sink-page work the device genuinely executes.  The
        numbers describe the compiled XLA module, which on CPU is the
        XLA reference attention, not the interpret-mode Pallas kernel
        (BASELINE round-17 honesty note)."""
        if self._efficiency_stats is not None:
            return self._efficiency_stats
        if not compute:
            return None
        if self._efficiency_failed:
            # a failed probe is cached too — the 'one extra AOT
            # compile ever' contract also covers the failure path (a
            # periodic refresh must not re-pay a multi-second failing
            # compile every sweep); the env gate is NOT a failure
            return None
        from ..observability.capacity import _cost_analysis_enabled
        if not _cost_analysis_enabled():
            return None
        try:
            # explicit budget sets only validate their TOP against the
            # all-decode pack, so budgets[0] can be far smaller —
            # probing it would amortize the weights over too few tokens
            # and inflate the per-token numbers
            base = self.max_batch_size * (self.spec_k + 1)
            T = min((b for b in self.token_budgets if b >= base),
                    default=self.token_budgets[-1])
            stats = self.mixed.compiled_stats(T)
        except Exception:                             # noqa: BLE001
            self._efficiency_failed = True
            return None
        if not stats.get("flops_per_token"):
            self._efficiency_failed = True
            return None
        self._efficiency_stats = {
            "step": "mixed",
            "tokens_per_launch": int(stats["tokens"]),
            "flops_per_token": float(stats["flops_per_token"]),
            "hbm_bytes_per_token": float(
                stats.get("hbm_bytes_per_token", 0.0)),
            "flops_per_launch": float(stats.get("flops", 0.0)),
            "source": "cost_analysis",
        }
        return self._efficiency_stats

    # ---- page allocation ------------------------------------------------
    def _try_alloc(self) -> Optional[int]:
        """Pop a free page, reclaiming unreferenced prefix-cache pages
        under pressure (eviction honors refcounts: only table entries
        no live request holds are dropped)."""
        c = self.caches[0]
        if not c._free and self.prefix_cache is not None:
            self.prefix_cache.evict(1)
            self._sync_prefix_stats()
        if not c._free:
            return None
        return c.allocate_block()

    def _sync_prefix_stats(self):
        """Publish the prefix cache's host-side stat counters (evictions
        by outcome, host-tier spills/hits/restores) into the
        process-wide metrics — diffed against the last published
        snapshot so every increment lands exactly once."""
        pc = self.prefix_cache
        if pc is None:
            return
        pub = self._pc_published
        for attr, metric in (
                ("evictions", self._m_evict_reclaimed),
                ("skipped_pinned", self._m_evict_skipped),
                ("spills", self._m_host_spills),
                ("host_hits", self._m_host_hits),
                ("restores", self._m_host_restores)):
            cur = getattr(pc, attr)
            delta = cur - pub.get(attr, 0)
            if delta:
                metric.inc(delta)
                pub[attr] = cur

    def _alloc_block(self) -> int:
        blk = self._try_alloc()
        if blk is None:
            raise RuntimeError(
                "PagedKVCache out of blocks (%d in pool) and nothing "
                "evictable" % self.caches[0].num_blocks)
        return blk

    # ---- admission (prefill) -------------------------------------------
    def _admit(self):
        for i in range(self.max_batch_size):
            if not self.waiting or self.slots[i] is not None:
                continue
            if not self._try_admit(self.waiting[0], i):
                break                   # no room yet: keep waiting (FIFO)
            self.waiting.pop(0)

    def _try_admit(self, req: GenerationRequest, slot: int) -> bool:
        """Match the prompt against the prefix cache, reserve pages and
        seat the request as "prefilling": its suffix chunks ride the
        fused step packed this same step().  Returns False — with NO
        side effects — when the pool cannot cover the request yet."""
        if req.parent_req is not None \
                and req.parent_req.state in ("waiting", "prefilling"):
            # n>1 group: wait for the parent generation's prefill to
            # publish the shared prefix pages, so this child admits as
            # a whole-prompt hit (ref++ + COW) instead of recomputing
            return False
        cache = self.caches[0]
        L = len(req.prompt_ids)
        matched: List[int] = []
        hit_len = 0
        cow = False
        if self.prefix_cache is not None:
            matched = self.prefix_cache.match(req.prompt_ids)
            hit_len = len(matched) * self.block_size
            if matched and hit_len >= L:
                # whole-prompt hit: re-run the last position to sample
                # the first token — the suffix write lands mid-page in
                # the final shared block, which therefore needs a
                # private copy (copy-on-write on the first partial page)
                hit_len = L - 1
                cow = True
        total_need = cache.blocks_needed(
            L + (1 if self.lazy_alloc else req.max_new_tokens))
        new_needed = total_need - len(matched) + (1 if cow else 0)
        available = len(cache._free)
        if self.prefix_cache is not None:
            available += self.prefix_cache.evictable_count(
                exclude=set(matched))
        if new_needed > available:
            return False

        # ---- commit ---------------------------------------------------
        if self.prefix_cache is not None:
            # literal label values: the metric lint pins label domains
            self._m_prefix_lookups.labels(
                outcome="hit" if matched else "miss").inc()
            if matched:
                self.prefix_cache.hits += 1
                self.prefix_cache.hit_tokens += hit_len
                self._m_prefix_hit_tokens.inc(hit_len)
            else:
                self.prefix_cache.misses += 1
        cache.share_blocks(matched)
        req.block_ids = list(matched)
        # evict the whole page deficit UP FRONT: one evict() call
        # spills every victim in ONE batched extract (the r11
        # transfer-count rule) — _alloc_block's evict(1) stays only as
        # the safety net.  Safe only AFTER share_blocks: the matched
        # pages now hold a second reference, so eviction skips them
        short = new_needed - len(cache._free)
        if short > 0 and self.prefix_cache is not None:
            self.prefix_cache.evict(short)
            self._sync_prefix_stats()
        if cow:
            from ..jit.serving_step import copy_block
            src = req.block_ids[-1]
            dst = self._alloc_block()
            copy_block(self.caches, src, dst)
            if self.draft_caches:
                # the draft pool shares page ids: its copy of the
                # shared page moves with the target's
                copy_block(self.draft_caches, src, dst)
            cache.free_sequence([src])      # drop this request's share
            req.block_ids[-1] = dst
        while len(req.block_ids) < total_need:
            req.block_ids.append(self._alloc_block())
        req.prefill_pos = hit_len
        req.prefix_hit_tokens = hit_len
        # a prefix hit fills the draft pool too (same page ids, written
        # by the publisher's mirrored chunks); the suffix chunks mirror
        # from prefill_pos on
        req.draft_len = hit_len
        req.slot = slot
        req.state = "prefilling"
        self.slots[slot] = req
        self.counters["requests_admitted"] += 1
        # ONE admission record (enqueue ts rides as an arg — the
        # tracer is on the admission path, so records are budgeted)
        self._admitted.append(req.req_id)
        self.tracer.event(req.req_id, "admit", slot=slot,
                          prefix_hit_tokens=hit_len,
                          prompt_tokens=L,
                          enqueue_ts=req.t_submit, step=self._step_no)
        return True

    # ---- chunked prefill ------------------------------------------------
    def _pending_chunks(self) -> int:
        n = 0
        for r in self.slots:
            if r is not None and r.state == "prefilling":
                rem = len(r.prompt_ids) - r.prefill_pos
                n += -(-rem // self.chunk_size)
        return n

    def _complete_prefill(self, req: GenerationRequest, first: int):
        slot = req.slot
        req.seq_len = len(req.prompt_ids)
        req.draft_len = req.seq_len        # draft pool mirrored the prompt
        req.state = "running"
        if self.prefix_cache is not None:
            # publish this prompt's full pages for future admissions
            self.prefix_cache.register(req.prompt_ids, req.block_ids)
        self._append_token(req, first)
        if self.slots[slot] is req:         # still running after budget
            self._tokens[slot] = first

    # ---- lazy page growth -----------------------------------------------
    def _grow_pages(self) -> List[int]:
        """Lazy mode: before the fused step runs, every running slot
        must own a real page for the position it writes this step
        (seq_len).  A slot that needs a page neither the pool nor
        prefix-cache eviction can supply is the VICTIM: it is finished
        early with ``truncated=True`` — its pages return to the pool
        (often unblocking the others) and the batch keeps decoding.
        step() never raises for pool exhaustion."""
        truncated = []
        for r in list(self.slots):
            if r is None or r.state != "running":
                continue
            need = self.caches[0].blocks_needed(r.seq_len + 1)
            grew = True
            while len(r.block_ids) < need:
                blk = self._try_alloc()
                if blk is None:
                    grew = False
                    break
                r.block_ids.append(blk)
            if not grew:
                r.truncated = True
                self._m_truncated.inc()
                self._finish(r)
                truncated.append(r.req_id)
        return truncated

    # ---- fused mixed prefill+decode step --------------------------------
    @staticmethod
    def _samp_row(req: GenerationRequest, seed_xor: int = 0) -> np.ndarray:
        """The request's packed sampling knobs: (temperature bits,
        top_k, top_p bits, seed) — fp knobs bitcast into the int32
        lane.  ``seed_xor`` derives the draft engine's independent
        proposal stream from the same request seed."""
        row = np.empty(4, np.int32)
        row[0] = np.float32(req.temperature).view(np.int32)
        row[1] = req.top_k
        row[2] = np.float32(req.top_p).view(np.int32)
        row[3] = (req.seed ^ seed_xor) & 0x7FFFFFFF
        return row

    def _fill_mixed_pack(self, mx, budgets, spans):
        """Fill one MixedStep pack from span tuples
        ``(req, tokens, start, n_draft, seed_xor, masked)``: the span's
        tokens land at global positions ``start..start+m-1`` (kv_len =
        start+m), pages from the request's block table, sampling-knob
        columns when the step compiles them.  ``masked`` spans keep the
        padding descriptor (writes to the sink page, all-sink block
        table) but still occupy their span row, so output/probs rows
        stay slot-aligned across launches.  Returns ``(pack, B)``."""
        total = sum(len(t) for _, t, _, _, _, _ in spans)
        B = next(b for b in budgets if b >= total)
        bs = self.block_size
        W = self.bt_width
        pack, tok_tab, span_tab = mx.new_pack(B)
        tokens, positions, dest_blocks, dest_offsets = tok_tab
        tokens[:] = 0
        positions[:] = 0
        # padding tokens: distinct sink-page slots (garbage on garbage)
        dest_blocks[:] = self._sink
        dest_offsets[:] = self._dest_pad[:B]
        # padding spans pin their offset past the last token so the
        # traced span-of-token search never maps a real token to them
        span_tab[:, :W] = self._sink
        span_tab[:, W] = B          # q_offset
        span_tab[:, W + 1] = 0      # q_len
        span_tab[:, W + 2] = 1      # kv_len
        span_tab[:, W + 3] = 0      # sample_row
        nd_col = W + 4 if mx.spec_k else -1
        sc = W + 4 + (1 if mx.spec_k else 0)
        off = 0
        for si, (r, toks, start, nd, sxor, masked) in enumerate(spans):
            m = len(toks)
            row = span_tab[si]
            row[W] = off
            row[W + 1] = m
            row[W + 3] = off + m - 1
            if masked:
                # keep the slot-aligned row but touch nothing live:
                # block table stays all-sink, writes stay on the sink
                # page, kv_len covers only the span itself
                row[W + 2] = m
                tokens[off:off + m] = toks
                positions[off:off + m] = np.arange(m, dtype=np.int32)
                off += m
                continue
            row[W + 2] = start + m
            row[:len(r.block_ids)] = r.block_ids
            if nd_col >= 0:
                row[nd_col] = nd
            if mx.sampling:
                row[sc:sc + 4] = self._samp_row(r, sxor)
            pos = np.arange(start, start + m, dtype=np.int32)
            tokens[off:off + m] = toks
            positions[off:off + m] = pos
            dest_blocks[off:off + m] = [r.block_ids[p // bs]
                                        for p in pos]
            dest_offsets[off:off + m] = pos % bs
            off += m
        return pack, B

    def _pack_spans(self):
        """Choose this step's ragged span set: every running slot's
        decode token (all must advance), then pending prefill chunks
        round-robin over prefilling slots while the TOP budget has room
        — multiple chunks per step, the round-robin latency killer.
        The chunk half is ``_pick_chunks`` — the ONE chunk-selection
        policy, shared with the speculative round's draft mirror."""
        spans = []                    # (req, kind, size, start)
        total = 0
        for r in self.slots:
            if r is not None and r.state == "running":
                spans.append((r, "decode", 1, r.seq_len))
                total += 1
        for r, size, start in self._pick_chunks(
                self.token_budgets[-1] - total):
            spans.append((r, "prefill", size, start))
            total += size
        return spans, total

    def _run_mixed_step(self) -> List[int]:
        """Pack the admission mix into ONE fused MixedStep launch: build
        the per-token and per-span tables on the host (control flow),
        pad to the smallest token budget, dispatch, then book every
        span's sampled token."""
        if self.draft_step is not None:
            return self._run_spec_round()
        rec = self._rec
        done = self._grow_pages() if self.lazy_alloc else []
        spans, total = self._pack_spans()
        if not spans:
            return done
        fill = [(r,
                 np.asarray([self._tokens[r.slot]], np.int32)
                 if kind == "decode"
                 else r.prompt_ids[start:start + size].astype(np.int32),
                 start, 0, 0, False)
                for r, kind, size, start in spans]
        rec["t_pack"] = self._phase("engine.fill")
        pack, B = self._fill_mixed_pack(self.mixed, self.token_budgets,
                                        fill)

        rec["t_fill"] = t0 = self._phase(None)
        pre = self.mixed.total_compiles
        nxt = self.mixed.call_packed(pack, B)
        rec["t_tokens"] = t1 = self._phase("engine.book")
        rec["t_dispatch"] = self.mixed.t_dispatch
        traced = self.mixed.total_compiles - pre
        dt = t1 - t0
        if self.tp is not None:
            self._count_collectives(self.mixed.collective_bytes(B))
        n_dec = sum(1 for _, kind, _, _ in spans if kind == "decode")
        n_pre = total - n_dec
        rec.update(budget=B, n_dec=n_dec, n_pre=n_pre,
                   compiled=bool(traced))
        if n_dec:
            self._m_mixed_tok_decode.inc(n_dec)
        if n_pre:
            self._m_mixed_tok_prefill.inc(n_pre)
        if self._moe_layers:
            # dropless dispatch: every real token lands on exactly
            # top_k experts per MoE layer, none are dropped
            self._m_moe_routed.inc(total * self._moe_topk
                                   * self._moe_layers)
        if self.mixed.n_stats:
            stats = self.mixed.last_stats
            rec.update(moe_rows=int(stats[0]), moe_rows_top=int(stats[1]),
                       moe_tiles=int(stats[2]))
            for child, n in zip(self._m_moe_expert, stats[3:]):
                child.inc(int(n))
        if traced:
            # first trace of this budget: count it, keep the compile
            # warmup out of every latency histogram
            self._m_mixed_compiles.inc(traced)
        else:
            # the fused step IS both the decode round and the prefill
            # round — classify its (warm) duration into whichever
            # histograms the pack actually advanced
            if n_dec:
                self._m_decode.observe(dt)
            if n_pre:
                self._m_prefill.observe(dt)
        if self.tracer.enabled:
            rec["spans"] = [(r.req_id, size, start + size)
                            for r, _, size, start in spans]
            # every span in the pack shares the one launch window
            for r, kind, size, start in spans:
                if kind == "decode":
                    self.tracer.sample_span(
                        r.req_id, "decode_step", t0, t1,
                        every=self.trace_decode_every,
                        step=self._step_no)
                else:
                    self.tracer.span(r.req_id, "prefill_chunk", t0, t1,
                                     offset=start, tokens=size,
                                     warm=not traced, step=self._step_no)

        for si, (r, kind, size, start) in enumerate(spans):
            tok = int(nxt[si])
            if kind == "decode":
                i = r.slot
                r.seq_len += 1
                self._append_token(r, tok)
                if self.slots[i] is r:
                    self._tokens[i] = tok
                if r.state == "done":
                    done.append(r.req_id)
            else:
                r.prefill_pos += size
                if r.prefill_pos >= len(r.prompt_ids):
                    # final chunk: tok is the on-device-sampled first
                    # token (earlier chunks' samples are discarded)
                    self._complete_prefill(r, tok)
                    if r.state == "done":
                        done.append(r.req_id)
        return done

    # ---- speculative decoding (draft_model=) ----------------------------
    def _spec_k_eff(self, req: GenerationRequest) -> int:
        """Draft depth for this request this round: never propose past
        the generation budget (a round emits at most k_eff+1 tokens)."""
        remaining = req.max_new_tokens - len(req.output_ids)
        return max(0, min(self.spec_k, remaining - 1))

    def _grow_spec_pages(self, keff: Dict[int, int]):
        """Lazy mode: pages for the k_eff draft positions past the
        mandatory seq_len write are OPPORTUNISTIC — when the pool can't
        cover a slot's full draft depth, the depth shrinks instead of
        truncating the request (the mandatory page was grown by
        ``_grow_pages`` already)."""
        c = self.caches[0]
        for r in self.slots:
            if r is None or r.state != "running":
                continue
            k = keff.get(r.slot, 0)
            while k > 0:
                need = c.blocks_needed(r.seq_len + 1 + k)
                ok = True
                while len(r.block_ids) < need:
                    blk = self._try_alloc()
                    if blk is None:
                        ok = False
                        break
                    r.block_ids.append(blk)
                if ok:
                    break
                k -= 1
            keff[r.slot] = k

    def _pick_chunks(self, room: int):
        """Pending prefill chunks for this round, round-robin over
        prefilling slots while ``room`` holds (the same policy as
        ``_pack_spans``; shared by the draft mirror and the verify
        pack, which must see identical chunk work)."""
        spans = []
        n = self.max_batch_size
        advanced_first = None
        for k in range(n):
            i = (self._chunk_rr + k) % n
            r = self.slots[i]
            if r is None or r.state != "prefilling":
                continue
            if room <= 0:
                break
            size = min(self.chunk_size,
                       len(r.prompt_ids) - r.prefill_pos, room)
            if size <= 0:
                continue
            spans.append((r, size, r.prefill_pos))
            room -= size
            if advanced_first is None:
                advanced_first = i
        if advanced_first is not None:
            self._chunk_rr = (advanced_first + 1) % n
        return spans

    def _run_draft_round(self, run_spans, chunk_spans, drafts):
        """The round's ``spec_k`` fused draft-model launches.  Launch 0
        packs every running slot's catch-up span (the 1-2 accepted
        tokens the draft pool hasn't seen, ending at the current token)
        TOGETHER with the round's prefill-chunk mirrors, so the draft
        pool prefills the same prompts in the same rounds; launches
        1..k-1 feed each freshly proposed token back.  A slot whose
        draft depth is capped below the launch index rides along
        MASKED (sink writes), keeping output rows slot-aligned.  Fills
        ``drafts[slot] = [d1..]``; returns the per-launch filtered
        proposal distributions (device-resident) for the verifier's
        rejection-resampling."""
        from ..ops.sampling import DRAFT_SEED_XOR
        q_list = []
        for i in range(self.spec_k):
            spans = []
            for r, k_eff in run_spans:
                # depth-capped slots stop feeding live pages (their
                # later proposals are never verified) — and a masked
                # span only needs ONE placeholder token to keep the
                # output/probs rows slot-aligned
                masked = i >= k_eff
                if i == 0 and not masked:
                    cu = r.seq_len + 1 - r.draft_len
                    toks = np.asarray(r.output_ids[-cu:], np.int32)
                    start = r.draft_len
                elif masked:
                    toks = np.asarray([r.output_ids[-1]], np.int32)
                    start = r.seq_len + i
                else:
                    toks = np.asarray([drafts[r.slot][i - 1]], np.int32)
                    start = r.seq_len + i
                spans.append((r, toks, start, 0, DRAFT_SEED_XOR,
                              masked))
            if i == 0:
                for r, size, start in chunk_spans:
                    spans.append(
                        (r, r.prompt_ids[start:start + size]
                         .astype(np.int32), start, 0, DRAFT_SEED_XOR,
                         False))
            if not spans:
                break
            t0 = time.perf_counter()
            pre = self.draft_step.total_compiles
            pack, B = self._fill_mixed_pack(self.draft_step,
                                            self.draft_budgets, spans)
            out = self.draft_step.call_packed(pack, B)
            if self.sampling:
                toks_np, probs = out
                q_list.append(probs)
            else:
                toks_np = out
            if self.draft_step.total_compiles == pre:
                self._m_draft_step.observe(time.perf_counter() - t0)
            for si, (r, _k) in enumerate(run_spans):
                drafts[r.slot].append(int(toks_np[si]))
            if not run_spans:
                break               # chunk mirror only, nothing to feed
        return q_list

    def _run_spec_round(self) -> List[int]:
        """One speculative engine round: k fused draft launches propose
        per-slot token chains, ONE fused MixedStep launch verifies all
        slots' k+1 positions (and advances prefill chunks riding the
        same pack), and the host applies the accepted prefix + the
        corrected/bonus token.  Greedy output is byte-identical to the
        non-speculative engine; sampled output is distribution-exact
        (rejection-resampling on device)."""
        done = self._grow_pages() if self.lazy_alloc else []
        keff: Dict[int, int] = {}
        for r in self.slots:
            if r is not None and r.state == "running":
                keff[r.slot] = self._spec_k_eff(r)
        if self.lazy_alloc:
            self._grow_spec_pages(keff)
        run_spans = [(r, keff[r.slot]) for r in self.slots
                     if r is not None and r.state == "running"]
        total_v = sum(k + 1 for _, k in run_spans)
        # chunk room must fit BOTH packs that carry the chunks: the
        # verify pack (k_eff+1 tokens per running slot) and the draft's
        # launch 0 (at most 2 catch-up tokens per running slot)
        chunk_spans = self._pick_chunks(
            min(self.token_budgets[-1] - total_v,
                self.draft_budgets[-1] - 2 * len(run_spans)))
        if not run_spans and not chunk_spans:
            return done

        drafts: Dict[int, List[int]] = {r.slot: [] for r, _ in run_spans}
        q_list = self._run_draft_round(run_spans, chunk_spans, drafts)

        v_spans = []
        for r, k_eff in run_spans:
            toks = np.empty(k_eff + 1, np.int32)
            toks[0] = self._tokens[r.slot]
            if k_eff:
                toks[1:] = drafts[r.slot][:k_eff]
            v_spans.append((r, toks, r.seq_len, k_eff, 0, False))
        for r, size, start in chunk_spans:
            v_spans.append((r, r.prompt_ids[start:start + size]
                            .astype(np.int32), start, 0, 0, False))
        rec = self._rec
        rec["t_pack"] = self._phase("engine.fill")
        pack, B = self._fill_mixed_pack(self.mixed, self.token_budgets,
                                        v_spans)
        q_probs = None
        if self.sampling:
            while len(q_list) < self.spec_k:
                q_list.append(self._zero_q)
            q_probs = tuple(q_list)

        rec["t_fill"] = t0 = self._phase(None)
        pre = self.mixed.total_compiles
        nxt, n_acc = self.mixed.call_packed(pack, B, q_probs=q_probs)
        rec["t_tokens"] = t1 = self._phase("engine.book")
        rec["t_dispatch"] = self.mixed.t_dispatch
        traced = self.mixed.total_compiles - pre
        dt = t1 - t0
        n_pre = sum(size for _, size, _ in chunk_spans)
        rec.update(budget=B, n_dec=len(run_spans), n_pre=n_pre,
                   compiled=bool(traced))
        if traced:
            self._m_mixed_compiles.inc(traced)
        else:
            if run_spans:
                self._m_decode.observe(dt)
            if n_pre:
                self._m_prefill.observe(dt)
        if n_pre:
            self._m_mixed_tok_prefill.inc(n_pre)
        if self.tracer.enabled:
            rec["spans"] = [(r.req_id, len(toks), start + len(toks))
                            for r, toks, start, _, _, _ in v_spans]
            # one verify launch advanced every slot (and the chunk
            # mirrors): sampled decode spans + chunk spans share its
            # window, exactly like the non-speculative mixed step
            for r, _k in run_spans:
                self.tracer.sample_span(
                    r.req_id, "decode_step", t0, t1,
                    every=self.trace_decode_every, speculative=True,
                    step=self._step_no)
            for r, size, start in chunk_spans:
                self.tracer.span(r.req_id, "prefill_chunk", t0, t1,
                                 offset=start, tokens=size,
                                 warm=not traced, step=self._step_no)

        emitted = 0
        for si, (r, toks, start, nd, _x, _m) in enumerate(v_spans):
            if r.state == "prefilling":
                r.prefill_pos += len(toks)
                if r.prefill_pos >= len(r.prompt_ids):
                    self._complete_prefill(r, int(nxt[si]))
                    if r.state == "done":
                        done.append(r.req_id)
                continue
            na = int(n_acc[si])
            k_eff = nd
            self._m_spec_proposed.inc(k_eff)
            self._m_spec_accepted.inc(na)
            # draft-pool correctness mark BEFORE advancing seq_len:
            # the slot's live launches fed cur@s and d1..d_{k_eff-1},
            # and the correct prefix ends at the last ACCEPTED fed
            # position — next round's catch-up span starts there
            if k_eff >= 1:
                r.draft_len = r.seq_len + 1 + min(na, k_eff - 1)
            out_toks = drafts[r.slot][:na] + [int(nxt[si])]
            for t in out_toks:
                r.seq_len += 1
                emitted += 1
                self._append_token(r, t)
                if r.state == "done":
                    done.append(r.req_id)
                    break
            if self.slots[r.slot] is r:
                self._tokens[r.slot] = r.output_ids[-1]
                if self.lazy_alloc:
                    # roll back pages grown for rejected draft
                    # positions through the refcounted release path
                    c = self.caches[0]
                    keep = len(c.trim_blocks(r.block_ids,
                                             r.seq_len + 1))
                    del r.block_ids[keep:]
        if emitted:
            self._m_mixed_tok_decode.inc(emitted)
        return done

    # ---- bookkeeping ----------------------------------------------------
    def _count_collectives(self, by_op: Dict[str, int]):
        """Publish one sharded dispatch's per-chip collective payload
        (host-side accounting — the byte counts are static per compiled
        shape, so nothing is fetched from the device).  When the logits
        all-gather is quantized, its (already-int8-sized) payload is
        additionally counted under the quantized-collective family."""
        if by_op.get("psum"):
            self._m_tp_psum.inc(by_op["psum"])
        if by_op.get("all_gather"):
            self._m_tp_all_gather.inc(by_op["all_gather"])
            if self.quant_collectives:
                self._m_quant_all_gather.inc(by_op["all_gather"])
        if by_op.get("cp_merge"):
            self._m_cp_all_gather.inc(by_op["cp_merge"])
        if by_op.get("ep_all_to_all"):
            self._m_ep_all_to_all.inc(by_op["ep_all_to_all"])
        if by_op.get("ep_all_gather"):
            self._m_ep_all_gather.inc(by_op["ep_all_gather"])
        if self._fsdp_gather_bytes:
            self._m_fsdp_gather.inc(self._fsdp_gather_bytes)

    def record_token_mismatches(self, n: int):
        """Feed the quant token-mismatch counter (callers: the paired
        fp32-vs-quant bench/test harnesses that actually know the
        reference tokens)."""
        if n:
            self._m_quant_mismatch.inc(int(n))

    def _append_token(self, req: GenerationRequest, token: int):
        req.output_ids.append(token)
        self.counters["tokens_generated"] += 1
        if len(req.output_ids) == 1:
            req.t_first_token = time.perf_counter()
            if req.t_submit:
                self._m_ttft.observe(req.t_first_token - req.t_submit)
            self.tracer.event(
                req.req_id, "first_token", ts=req.t_first_token,
                ttft=(req.t_first_token - req.t_submit
                      if req.t_submit else 0.0), step=self._step_no)
        hit_eos = (req.eos_token_id is not None
                   and token == req.eos_token_id)
        if len(req.output_ids) >= req.max_new_tokens or hit_eos:
            self._finish(req)

    def _release_slot(self, req: GenerationRequest):
        """Mask the request's slot back to the sink page and release
        its pages through the ONE refcounted path.  Shared by
        ``_finish`` and ``preempt_request`` — every per-slot state
        field must be cleared HERE and nowhere else, so the two release
        sites cannot drift as new fields are added."""
        if req.slot >= 0:
            self.slots[req.slot] = None
            self._tokens[req.slot] = 0
        # the SINGLE release path: refcounted — pages shared with the
        # prefix table or another live request survive this drop
        self.caches[0].free_sequence(req.block_ids)
        req.block_ids = []

    def _finish(self, req: GenerationRequest):
        req.state = "done"
        n_tok = len(req.output_ids)
        self._m_requests.labels(
            outcome="truncated" if req.truncated else "completed").inc()
        self._m_tokens.inc(n_tok)
        req.t_done = time.perf_counter()
        if n_tok > 1 and req.t_first_token:
            self._m_tpot.observe(
                (req.t_done - req.t_first_token) / (n_tok - 1))
        self.tracer.event(
            req.req_id, "finish", ts=req.t_done, tokens=n_tok,
            outcome="truncated" if req.truncated else "completed")
        self._release_slot(req)
        self.finished[req.req_id] = req
