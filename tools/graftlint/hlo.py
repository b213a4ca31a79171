"""Compiled-artifact contract rules (family 2) — ``graftlint.hlo``.

Generalizes the ``verify_sharded_update`` HLO assertions
(``distributed/auto_parallel/dist_model.py``) into a reusable pass:
AOT-lower the fused train step and the serving step ONCE over a
tiny 1-layer model on CPU (≈2s total; artifacts are cached per
process) and assert, from the optimized HLO text and the lowered
operand avals, the three contracts every round since r11 has ridden
on:

- **hlo-donation**: buffer donation actually aliases the KV pools
  (and the train step's params/opt-states) — the compiled module's
  ``input_output_alias`` table covers every pool parameter.  A donation
  that silently stops aliasing (a dtype/layout mismatch, a new operand
  inserted before the pools) doubles pool HBM and turns the in-place
  cache append into a copy; nothing crashes, serving just slows down.
- **hlo-f64**: no ``f64`` op anywhere in any compiled step.  x64 is
  globally on (paddle int64 parity), so one stray Python float staged
  at trace time silently doubles HBM and falls off the MXU path — the
  trace-safety rule catches the line, this rule proves the artifact.
- **hlo-packed-layout**: the operand pytree matches the pinned layout.
  The mixed step carries exactly ONE int32 host operand of exactly
  ``4*T + max_spans*(bt_width+4)`` words (the round-11 "nine operands,
  one transfer" rule: transfer COUNT is the decode budget).  A new
  host operand — however small — is a second per-step transfer and
  fails here, not in a TPU latency regression three rounds later.

The check functions are pure text/aval predicates so the self-test can
feed doctored artifacts; only :func:`build_artifacts` imports jax.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from .core import Finding, Rule, register

__all__ = ["Artifact", "build_artifacts", "check_donation",
           "check_no_f64", "check_packed_layout", "parse_alias_pairs",
           "parse_entry_param_types"]

# the tiny-model envelope the artifacts are built at (1 layer keeps
# compile ~0.5s/step; the contracts are shape-generic)
TINY = dict(num_hidden_layers=1, hidden_size=32, num_attention_heads=2,
            num_key_value_heads=2, vocab_size=64, intermediate_size=64)
NUM_BLOCKS, BLOCK_SIZE = 8, 4
BT_WIDTH, MAX_SPANS = 4, 2
MIXED_T = 8
# round 21: the 2D fsdp x tp mesh the extra artifacts lower under —
# every TINY dim divides by 2, so the composed specs survive pruning
MESH_FSDP, MESH_TP = 2, 2


@dataclass
class Artifact:
    """One compiled step: its optimized HLO text, the lowered operand
    avals (as (dtype_name, shape) pairs) and the pinned expectations."""
    name: str
    text: str
    avals: List[Tuple[str, Tuple[int, ...]]]
    n_pool_params: int            # pool leaves that must alias
    pool_sig: Optional[str]       # e.g. "f32[8,4,2,16]" (None: train)
    expect_i32: Optional[int]     # pinned int32 host-operand count
    packed_len: Optional[int]     # pinned single-pack length (mixed)
    min_aliases: int = 0          # lower bound on alias entries


# -- pure text/aval predicates (self-testable) ------------------------------
_ALIAS_PAIR_RE = re.compile(r"\{[\d,\s]*\}:\s*\((\d+)")


def parse_alias_pairs(text: str) -> List[int]:
    """Parameter indices the compiled module aliases into outputs."""
    head = text.split("\n", 1)[0]
    m = re.search(r"input_output_alias=\{(.*)", head)
    if not m:
        return []
    return [int(p) for p in _ALIAS_PAIR_RE.findall(m.group(1))]


def parse_entry_param_types(text: str) -> List[str]:
    """The entry computation's parameter type list, layout stripped
    (``['s32[8]', 'f32[8,4,2,16]', ...]``)."""
    head = text.split("\n", 1)[0]
    m = re.search(r"entry_computation_layout=\{\((.*?)\)->", head)
    if not m:
        return []
    sig = re.sub(r"/\*.*?\*/", "", m.group(1))   # strip /*index=N*/
    out = []
    for tok in sig.split(", "):
        tok = tok.strip()
        if tok:
            out.append(tok.split("{")[0])
    return out


def check_donation(art: Artifact) -> List[Finding]:
    aliased = parse_alias_pairs(art.text)
    out: List[Finding] = []
    where = f"<{art.name}>"
    if len(aliased) < art.min_aliases:
        out.append(Finding(
            "hlo-donation", where, 0,
            f"compiled module aliases {len(aliased)} parameter(s) but "
            f"donation pins at least {art.min_aliases} — a donated "
            f"buffer stopped aliasing (layout/dtype mismatch or an "
            f"operand inserted before the pools); the in-place update "
            f"became a copy"))
    if art.pool_sig is not None:
        params = parse_entry_param_types(art.text)
        pool_idx = [i for i, t in enumerate(params) if t == art.pool_sig]
        if len(pool_idx) < art.n_pool_params:
            out.append(Finding(
                "hlo-donation", where, 0,
                f"expected {art.n_pool_params} pool parameter(s) of "
                f"type {art.pool_sig} in the entry signature, found "
                f"{len(pool_idx)} — the KV pools no longer reach the "
                f"module as parameters"))
        missing = [i for i in pool_idx if i not in aliased]
        if missing:
            out.append(Finding(
                "hlo-donation", where, 0,
                f"KV pool parameter(s) {missing} ({art.pool_sig}) are "
                f"NOT in the input_output_alias table — the cache "
                f"append is compiling as a copy, doubling pool HBM"))
    return out


def check_no_f64(art: Artifact) -> List[Finding]:
    hits = [i + 1 for i, line in enumerate(art.text.splitlines())
            if "f64[" in line]
    if not hits:
        return []
    return [Finding(
        "hlo-f64", f"<{art.name}>", 0,
        f"compiled module stages f64 ops ({len(hits)} HLO line(s), "
        f"first at text line {hits[0]}) — a Python float/np.float64 "
        f"leaked into the trace under global x64; 2x HBM, off the "
        f"MXU path")]


def check_packed_layout(art: Artifact) -> List[Finding]:
    out: List[Finding] = []
    where = f"<{art.name}>"
    if art.expect_i32 is not None:
        i32 = [(dt, shp) for dt, shp in art.avals if dt == "int32"]
        if len(i32) != art.expect_i32:
            out.append(Finding(
                "hlo-packed-layout", where, 0,
                f"{len(i32)} int32 host operand(s) in the lowered "
                f"signature, pinned layout says {art.expect_i32} — "
                f"every extra operand is an extra per-step host "
                f"transfer (round-11: transfer COUNT is the decode "
                f"budget); pack it into the existing buffer"))
        if art.packed_len is not None:
            lens = [shp for _dt, shp in i32]
            if not any(shp == (art.packed_len,) for shp in lens):
                out.append(Finding(
                    "hlo-packed-layout", where, 0,
                    f"no int32[{art.packed_len}] pack operand in the "
                    f"lowered signature (got {lens}) — the mixed "
                    f"step's pack no longer matches the pinned "
                    f"4*T + max_spans*(bt_width+4) layout; update the "
                    f"pin ONLY with the engine-side pack writer"))
    return out


# -- artifact construction (jax only from here down) ------------------------
_ARTIFACTS: Dict[str, Artifact] = {}


def _avals_of(lowered) -> List[Tuple[str, Tuple[int, ...]]]:
    import jax
    leaves = jax.tree_util.tree_leaves(lowered.in_avals)
    return [(str(a.dtype), tuple(a.shape)) for a in leaves]


def build_artifacts() -> Dict[str, Artifact]:
    """Build + compile the step artifacts once per process (tiny
    1-layer model, CPU platform — deterministic anywhere): the 1D
    lowerings (mixed step, migration inject, train step) plus the
    round-21 fsdp x tp pair (2D mixed step and 2D train step)."""
    if _ARTIFACTS:
        return _ARTIFACTS
    from paddle_tpu.testing.dryrun import force_cpu_devices
    # 4 virtual devices: the 1D artifacts still lower single-chip
    # (their HLO is device-count independent), and the round-21
    # fsdp x tp artifacts get their (2,2) mesh
    force_cpu_devices(MESH_FSDP * MESH_TP)
    import paddle_tpu as paddle

    # seed for deterministic artifacts, but restore the ambient RNG
    # stream when done — the in-suite tier-1 smoke must not perturb
    # tests that run after it
    rng_state = paddle.get_rng_state()
    paddle.seed(0)
    try:
        return _build_artifacts_seeded()
    finally:
        paddle.set_rng_state(rng_state)


def _build_artifacts_seeded() -> Dict[str, Artifact]:
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    from paddle_tpu.ops.paged_attention import PagedKVCache
    from paddle_tpu.jit.serving_step import MixedStep
    cfg = llama_tiny_config(**TINY)
    model = LlamaForCausalLM(cfg)
    model.eval()
    L = cfg.num_hidden_layers
    D = cfg.hidden_size // cfg.num_attention_heads
    Hkv = cfg.num_key_value_heads

    def caches():
        return [PagedKVCache(NUM_BLOCKS, BLOCK_SIZE, Hkv, D,
                             sink_block=True) for _ in range(L)]

    # the pool signature from the pool itself (sink_block adds a
    # physical page past NUM_BLOCKS)
    probe = caches()[0].key_cache
    pool_sig = "f32[" + ",".join(str(d) for d in probe.shape) + "]"

    def art(name, lowered, n_pool, psig, expect_i32, packed_len,
            min_aliases):
        avals = _avals_of(lowered)
        text = lowered.compile().as_text()
        _ARTIFACTS[name] = Artifact(
            name=name, text=text, avals=avals, n_pool_params=n_pool,
            pool_sig=psig, expect_i32=expect_i32,
            packed_len=packed_len, min_aliases=min_aliases)

    mixed = MixedStep(model, caches(), bt_width=BT_WIDTH,
                      max_spans=MAX_SPANS, use_pallas=False)
    packed_len = 4 * MIXED_T + MAX_SPANS * (BT_WIDTH + mixed.row_extra)
    art(f"mixed_step@T{MIXED_T}", mixed.aot_lower(MIXED_T),
        n_pool=2 * L, psig=pool_sig, expect_i32=1,
        packed_len=packed_len, min_aliases=2 * L)

    # round 19: the page-migration inject dispatch — every pool
    # parameter donated (the scatter is an in-place HBM write) and
    # exactly ONE int32 host operand (the destination page ids; the
    # page payload is the single buffer operand per dtype), so the
    # one-transfer migration rule is machine-checked like the steps'
    from paddle_tpu.jit.serving_step import _inject_j
    mig_pools = caches()
    kcs = tuple(c.key_cache for c in mig_pools)
    vcs = tuple(c.value_cache for c in mig_pools)
    n_pages = 2
    codes = np.zeros((2 * L, n_pages, BLOCK_SIZE, Hkv, D), np.float32)
    ids = np.zeros((n_pages,), np.int32)
    art(f"inject_blocks@P{n_pages}",
        _inject_j.lower(kcs, vcs, codes, ids),
        n_pool=2 * L, psig=pool_sig, expect_i32=1, packed_len=None,
        min_aliases=2 * L)

    import paddle_tpu.nn as nn
    from paddle_tpu.jit.train_step import TrainStep
    net = nn.Linear(8, 4)
    opt = paddle.optimizer.SGD(0.1, parameters=net.parameters())
    step = TrainStep(net, lambda o, y: ((o - y) ** 2).mean(), opt)
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    y = paddle.to_tensor(np.ones((4, 4), np.float32))
    n_params = len(net.state_dict())
    art("train_step", step.lower(x, y), n_pool=0, psig=None,
        expect_i32=None, packed_len=None, min_aliases=n_params)

    # round 21: the same contracts under a 2D fsdp x tp mesh — the
    # r18 artifacts above only pin the 1D lowerings, and 2D
    # in/out_shardings are exactly where donation aliasing and the
    # one-packed-operand rule can silently regress (a resharding
    # inserted between a donated operand and its output kills the
    # alias; an fsdp gather staged OUTSIDE the shard_map would surface
    # as a new host operand)
    from paddle_tpu.jit.spmd import ShardingConfig, mesh_2d
    mesh2d = mesh_2d(MESH_FSDP, MESH_TP)
    mixed2d = MixedStep(model, caches(), bt_width=BT_WIDTH,
                        max_spans=MAX_SPANS,
                        use_pallas=False, mesh=mesh2d)
    # the sharded module's entry layout is PER-SHARD: the pool's kv
    # heads arrive already divided by tp (fsdp never names the pools)
    shard_shape = list(probe.shape)
    shard_shape[2] //= MESH_TP
    pool_sig_2d = "f32[" + ",".join(str(d) for d in shard_shape) + "]"
    art(f"mixed_step_2d@T{MIXED_T}", mixed2d.aot_lower(MIXED_T),
        n_pool=2 * L, psig=pool_sig_2d, expect_i32=1,
        packed_len=packed_len, min_aliases=2 * L)

    # round 22: the same contracts under a cp=2 context-parallel mesh
    # — the pools enter SLOT-striped (block_size/cp per chip), the
    # stripe-merge all_gather must not break donation aliasing, and
    # the packed int32 operand stays the ONE host transfer (the
    # stripe-local destination translation is traced math, not a new
    # operand)
    from paddle_tpu.jit.spmd import cp_mesh
    MESH_CP = 2
    meshcp = cp_mesh(MESH_CP)
    mixedcp = MixedStep(model, caches(), bt_width=BT_WIDTH,
                        max_spans=MAX_SPANS,
                        use_pallas=False, mesh=meshcp)
    cp_shard_shape = list(probe.shape)
    cp_shard_shape[1] //= MESH_CP
    pool_sig_cp = "f32[" + ",".join(str(d) for d in cp_shard_shape) \
        + "]"
    art(f"mixed_step_cp@T{MIXED_T}", mixedcp.aot_lower(MIXED_T),
        n_pool=2 * L, psig=pool_sig_cp, expect_i32=1,
        packed_len=packed_len, min_aliases=2 * L)

    # round 24: the same contracts under an ep=2 expert-parallel mesh
    # with a tiny Mixtral — the MoE dispatch's all_to_all pair and
    # token all_gather must not break donation aliasing (the pools
    # enter UNsharded: ep never names a pool dim), and the routing
    # tables are traced math over the one packed operand, never a new
    # host transfer
    from paddle_tpu.models.mixtral import (MixtralForCausalLM,
                                           mixtral_tiny_config)
    from paddle_tpu.jit.spmd import ep_mesh
    MESH_EP = 2
    moe_cfg = mixtral_tiny_config(
        **TINY, num_local_experts=2, num_experts_per_tok=1)
    moe_model = MixtralForCausalLM(moe_cfg)
    moe_model.eval()
    meshep = ep_mesh(MESH_EP)
    mixedep = MixedStep(moe_model, caches(), bt_width=BT_WIDTH,
                        max_spans=MAX_SPANS,
                        use_pallas=False, mesh=meshep)
    art(f"mixed_step_ep@T{MIXED_T}", mixedep.aot_lower(MIXED_T),
        n_pool=2 * L, psig=pool_sig, expect_i32=1,
        packed_len=packed_len, min_aliases=2 * L)

    model2d = LlamaForCausalLM(cfg)
    opt2d = paddle.optimizer.SGD(0.1,
                                 parameters=model2d.parameters())

    def lm_loss(logits, labels):
        import paddle_tpu.nn.functional as F
        return F.cross_entropy(
            logits.reshape([-1, logits.shape[-1]]),
            labels.reshape([-1]))

    step2d = TrainStep(model2d, lm_loss, opt2d, mesh=mesh2d,
                       sharding=ShardingConfig(axis="fsdp"))
    ids2d = paddle.to_tensor(
        np.zeros((MESH_FSDP * MESH_TP, 8), np.int64))
    art("train_step_2d", step2d.lower(ids2d, ids2d), n_pool=0,
        psig=None, expect_i32=None, packed_len=None,
        min_aliases=len(model2d.state_dict()))
    return _ARTIFACTS


def _run(checker) -> List[Finding]:
    out: List[Finding] = []
    for a in build_artifacts().values():
        out.extend(checker(a))
    return out


def _doctored(name: str, **kw) -> Artifact:
    base = dict(
        name=name,
        text="HloModule jit_step, entry_computation_layout="
             "{(s32[48]{0}, f32[8,4,2,16]{3,2,1,0})->(s32[])}\n"
             "  %x = f64[2,3] parameter(0)\n",
        avals=[("int32", (48,)), ("int32", (7,))],
        n_pool_params=1, pool_sig="f32[8,4,2,16]", expect_i32=1,
        packed_len=48, min_aliases=2)
    base.update(kw)
    return Artifact(**base)


register(Rule(
    id="hlo-donation",
    family="hlo-contracts",
    contract="the compiled train + serving steps' (and the migration "
             "inject dispatch's) input_output_alias tables cover every "
             "donated KV pool (and the train params) — in-place "
             "updates never silently become copies",
    check=lambda sources: _run(check_donation),
    # defect: a module whose alias table is empty
    selftest=lambda: check_donation(_doctored("inj-donation")),
    slow=True,
))

register(Rule(
    id="hlo-f64",
    family="hlo-contracts",
    contract="no f64 op appears in any compiled step artifact (x64 is "
             "globally on; f64 is 2x HBM and off the MXU path)",
    check=lambda sources: _run(check_no_f64),
    # defect: an artifact carrying one f64 HLO line
    selftest=lambda: check_no_f64(_doctored("inj-f64")),
    slow=True,
))

register(Rule(
    id="hlo-packed-layout",
    family="hlo-contracts",
    contract="the mixed step carries exactly ONE int32 host operand of "
             "the pinned 4*T+max_spans*(bt_width+4) length; the "
             "migration inject dispatch carries exactly one (the "
             "destination ids — payload is one buffer per dtype)",
    check=lambda sources: _run(check_packed_layout),
    # defect: a second int32 host operand rides along
    selftest=lambda: check_packed_layout(_doctored("inj-packed")),
    slow=True,
))
