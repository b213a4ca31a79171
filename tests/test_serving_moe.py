"""Expert-parallel MoE serving (round-24 tentpole).

Runs on the conftest-forced 8-device CPU mesh (the shared dryrun setup,
paddle_tpu/testing/dryrun.py).  An ``ep`` mesh axis shards every MoE
expert bank's E dim — chip r holds experts ``[r*E/ep, (r+1)*E/ep)`` of
every layer's w_gate/w_up/w_down stack — and the fused MixedStep routes
the packed span tokens through the ONE shared gate/dispatch helper set
(ops/moe_gate.py): top-k gate, dropless scatter into capacity buffers,
an all_to_all pair over the ep axis around the grouped expert SwiGLU,
and a weighted combine, all inside the one compiled launch.  The
contract gated here:

- tokens BYTE-IDENTICAL to the eager Mixtral ``generate`` AND the
  single-chip mixed engine on the same workload (ep=2 in tier-1; ep=4,
  ep x tp, per-expert int8 PTQ, prefix-COW and the heterogeneous
  dense+MoE router pool in the slow lane);
- per-chip expert-bank weights exactly 1/ep (the router + attention
  stay replicated/tp-sharded as before);
- compile count still bounded by the token-budget-set size (the MoE
  path adds no budgets and no host operands);
- the incubate gates and the serving dispatch share one gate
  implementation (bitwise identity);
- actionable construction-time errors for a non-dividing expert count,
  spec-decode and non-dividing token budgets under ep.

Budget note: the tier-1 suite runs AT the 870s timeout — only the ep=2
parity test, the (sub-second) gate-identity test and the validation
test are unmarked; every sweep is @slow.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.testing.dryrun import force_cpu_devices

force_cpu_devices(8)     # no-op under conftest; the documented entry

from paddle_tpu.inference.serving import (  # noqa: E402
    ContinuousBatchingEngine)
from paddle_tpu.jit.spmd import ep_mesh, validate_ep_serving  # noqa: E402

PROMPTS = [np.array([7, 9, 2], np.int64),
           np.array([3, 14, 15, 92, 65], np.int64),
           np.arange(1, 11, dtype=np.int64)]     # 10 -> chunked


def _model(seed=0, **kw):
    from paddle_tpu.models.mixtral import (MixtralForCausalLM,
                                           mixtral_tiny_config)
    paddle.seed(seed)
    cfg = mixtral_tiny_config(num_hidden_layers=2, **kw)
    model = MixtralForCausalLM(cfg)
    model.eval()
    return model


def _ref_tokens(model, prompt, n):
    out = model.generate(paddle.to_tensor(np.asarray(prompt)[None, :]),
                         max_new_tokens=n)
    return np.asarray(out._value)[0, len(prompt):].tolist()


def _run(model, mesh=None, budget=4, **kw):
    kw.setdefault("prefill_chunk_size", 4)
    eng = ContinuousBatchingEngine(model, max_batch_size=4,
                                   num_blocks=64, block_size=4,
                                   mesh=mesh, **kw)
    rids = []
    for i, p in enumerate(PROMPTS):
        rids.append(eng.add_request(p, budget))
        if i == 0:
            eng.step()          # stagger: r0 decodes while r1/r2 admit
    eng.run_to_completion()
    return eng, [eng.result(r) for r in rids]


def test_gate_helpers_shared_and_bitwise_identical():
    """Satellite 2: the incubate gates route through the ONE
    ``ops.moe_gate.topk_gate`` used by the Mixtral block and the fused
    serving dispatch — bitwise-identical weights/indices, and the
    Switch gate keeps its raw (un-renormalized) top-1 probability."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.moe_gate import topk_gate
    from paddle_tpu.incubate.distributed.models.moe.gate import (
        NaiveGate, SwitchGate)
    paddle.seed(0)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((16, 8)).astype(np.float32)

    gate = NaiveGate(8, 4, topk=2)
    w, i, aux = gate(paddle.to_tensor(x))
    logits = jnp.asarray(x) @ gate.weight._value
    rw, ri, _ = topk_gate(logits, 2)
    np.testing.assert_array_equal(np.asarray(i._value), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(w._value), np.asarray(rw))
    # the top-k weights renormalize to 1 per token
    np.testing.assert_allclose(np.asarray(w._value).sum(-1), 1.0,
                               rtol=1e-6)

    sw = SwitchGate(8, 4)
    w1, i1, aux1 = sw(paddle.to_tensor(x))
    probs = jax.nn.softmax(jnp.asarray(x) @ sw.weight._value, axis=-1)
    picked = np.take_along_axis(np.asarray(probs),
                                np.asarray(i1._value), axis=-1)
    # raw routing probability, NOT renormalized to 1.0
    np.testing.assert_allclose(np.asarray(w1._value), picked, rtol=1e-6)
    assert np.all(np.asarray(w1._value) < 1.0)
    assert aux1 is not None


def test_ep2_mixed_parity_expert_shard_and_compile_bound():
    """ep=2 fused mixed step: tokens byte-identical to BOTH the eager
    Mixtral ``generate`` and the single-chip mixed engine under
    admission churn, expert banks sharded 1/ep per chip, compiles
    bounded by the budget-set size, and the ep metrics published."""
    import jax
    model = _model()
    refs = [_ref_tokens(model, p, 4) for p in PROMPTS]
    e1, t1 = _run(model)
    assert t1 == refs, "single-chip mixed step diverged from eager"
    e2, t2 = _run(model, mesh=ep_mesh(2))
    assert t2 == refs, "ep=2 tokens diverged from the eager reference"
    assert e2.ep_degree == 2 and e2.tp_degree == 1
    assert e2.mixed.total_compiles <= len(e2.token_budgets)
    # expert banks carry P('ep') on their E dim; router + norms stay
    # replicated (the gate's top-k ties must match eager everywhere)
    bank_key = "mixtral.layers.0.block_sparse_moe.w_gate"
    spec = e2.tp.specs[bank_key]
    assert tuple(spec)[0] == "ep" \
        and all(ax is None for ax in tuple(spec)[1:]), spec
    router_key = "mixtral.layers.0.block_sparse_moe.gate.weight"
    assert all(ax is None for ax in e2.tp.specs[router_key])
    # placed under that spec, each chip holds exactly E/ep experts
    bank = model.state_dict()[bank_key]._value
    placed = jax.device_put(bank, e2.tp.named(spec))
    shard = placed.addressable_shards[0]
    assert shard.data.shape[0] * 2 == bank.shape[0], \
        "per-chip expert-bank slice is not 1/ep"
    # metrics: degree gauge, mesh axis, dispatch fates, payload bytes
    from paddle_tpu.observability import default_registry
    r = default_registry()
    assert r.get("serving_ep_degree").value == 2.0
    assert r.get("serving_mesh_shape").labels(axis="ep").value == 2.0
    disp = r.get("serving_moe_dispatch_tokens_total")
    assert disp.labels(fate="routed").value > 0
    assert disp.labels(fate="dropped").value == 0    # dropless
    coll = r.get("serving_ep_collective_bytes_total")
    assert coll.labels(op="all_to_all").value > 0
    assert coll.labels(op="all_gather").value > 0


def test_ep_validation_errors_at_construction():
    """Invalid ep geometries must fail engine construction with an
    actionable message — not a shard_map shape error deep in tracing:
    an expert count ep doesn't divide and non-dividing token budgets
    are rejected; spec-decode is rejected by the shared validator."""
    with pytest.raises(ValueError, match="divide"):
        ContinuousBatchingEngine(_model(num_local_experts=3),
                                 max_batch_size=2, num_blocks=16,
                                 block_size=4,
                                 prefill_chunk_size=4,
                                 mesh=ep_mesh(2))   # 3 % 2 != 0
    model = _model()
    with pytest.raises(ValueError, match="budget"):
        ContinuousBatchingEngine(model, max_batch_size=2, num_blocks=16,
                                 block_size=4,
                                 prefill_chunk_size=4,
                                 token_budgets=(3, 8),
                                 mesh=ep_mesh(2))   # 3 % 2 != 0
    with pytest.raises(ValueError, match="speculative"):
        validate_ep_serving(4, 2, spec_decode=True)
    # ep=1 degenerates to the plain single-chip engine
    eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                   num_blocks=16, block_size=4,
                                   mesh=ep_mesh(1))
    assert eng.tp is None and eng.ep_degree == 1


@pytest.mark.slow
def test_ep4_mixed_parity():
    """ep=4 (one expert per chip with the tiny E=4 bank): byte parity
    with eager + compile bound."""
    model = _model()
    refs = [_ref_tokens(model, p, 4) for p in PROMPTS]
    e4, t4 = _run(model, mesh=ep_mesh(4))
    assert t4 == refs
    assert e4.ep_degree == 4
    assert e4.mixed.total_compiles <= len(e4.token_budgets)


@pytest.mark.slow
def test_ep2_tp2_composed_parity():
    """ep x tp on one 2x2 mesh: expert shards compose with Megatron
    head/vocab shards — byte parity with the eager reference, both
    degrees resolved, and the attention families still carry the tp
    axis while the expert banks carry ep."""
    model = _model()
    refs = [_ref_tokens(model, p, 4) for p in PROMPTS]
    ec, tc = _run(model, mesh=ep_mesh(2, tp=2))
    assert tc == refs
    assert ec.ep_degree == 2 and ec.tp_degree == 2
    q_spec = ec.tp.specs["mixtral.layers.0.self_attn.q_proj.weight"]
    assert "tp" in tuple(q_spec)
    assert tuple(ec.tp.specs[
        "mixtral.layers.0.block_sparse_moe.w_up"])[0] == "ep"


@pytest.mark.slow
def test_ep2_int8_expert_ptq_parity_and_tolerance():
    """Per-expert int8 PTQ under ep=2: the quantized engine is
    byte-identical to the quantized SINGLE-CHIP engine (the dequant
    happens inside the step, per expert, before the all_to_all), and
    within token tolerance of the fp engine; the expert banks' scales
    are full-rank [E, 1, out] so the E dim shards."""
    from paddle_tpu.quantization.functional import quantize_param_tree
    model = _model()
    qtree = quantize_param_tree(
        {k: t._value for k, t in model.state_dict().items()})
    bank = "mixtral.layers.0.block_sparse_moe.w_gate"
    assert qtree[bank].dtype == np.int8
    assert qtree[bank + "::scale"].shape == (4, 1, 128)
    # router stays fp
    assert qtree["mixtral.layers.0.block_sparse_moe.gate.weight"].dtype \
        != np.int8

    _, tq1 = _run(model, weight_quant="int8")
    _, tq2 = _run(model, mesh=ep_mesh(2), weight_quant="int8")
    assert tq2 == tq1, "ep=2 int8 diverged from single-chip int8"
    _, tfp = _run(model)
    flat_q = [t for ts in tq2 for t in ts]
    flat_fp = [t for ts in tfp for t in ts]
    mismatch = sum(1 for a, b in zip(flat_q, flat_fp) if a != b)
    assert mismatch <= len(flat_fp) // 2, \
        f"int8 PTQ token mismatch rate too high: {mismatch}/{len(flat_fp)}"


@pytest.mark.slow
def test_ep_prefix_cache_cow_parity_and_leak_free():
    """Prefix-cache sharing and the whole-prompt-hit copy-on-write page
    copy must survive expert-sharded weights (pages, refcounts and COW
    stay chip-local — ep never names a pool dim): byte parity,
    refcounts settle, no page leaked."""
    model = _model()
    P = np.array([5, 17, 42, 7, 99, 3, 11, 23], np.int64)
    B = np.concatenate([P, [77, 8]])

    def run(mesh):
        eng = ContinuousBatchingEngine(
            model, max_batch_size=2, num_blocks=32, block_size=4,
            prefill_chunk_size=4,
            enable_prefix_cache=True, mesh=mesh)
        ra = eng.add_request(P, 4)
        eng.run_to_completion()
        rb = eng.add_request(B, 4)
        rc = eng.add_request(P, 4)       # whole-prompt hit -> COW
        eng.run_to_completion()
        return eng, [eng.result(r) for r in (ra, rb, rc)]

    e1, t1 = run(None)
    e2, t2 = run(ep_mesh(2))
    assert t2 == t1
    pc = e2.prefix_cache
    cached = pc.cached_blocks()
    c0 = e2.caches[0]
    assert all(c0.refcount(b) == 1 for b in cached)
    assert len(c0._free) + len(cached) == c0.num_blocks


@pytest.mark.slow
def test_router_pool_mixes_dense_and_moe_engines():
    """The round-15 router drives a heterogeneous pool — an ep=2 MoE
    Mixtral engine, a single-chip MoE engine and a dense Llama engine —
    through the unchanged dispatch/drain state machine: an engine death
    mid-flight requeues its work with ZERO drops (every request
    finishes its full budget) and the dead pool drains leak-free."""
    from paddle_tpu.inference.router import ServingRouter
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    moe = _model()
    paddle.seed(1)
    dense_cfg = llama_tiny_config(num_hidden_layers=2, hidden_size=64,
                                  num_attention_heads=4,
                                  num_key_value_heads=4,
                                  vocab_size=256,
                                  intermediate_size=128)
    dense = LlamaForCausalLM(dense_cfg)
    dense.eval()

    def eng(model, mesh=None):
        return ContinuousBatchingEngine(
            model, max_batch_size=2, num_blocks=32, block_size=4,
            prefill_chunk_size=4, mesh=mesh)

    e_moe_ep = eng(moe, ep_mesh(2))
    e_moe = eng(moe)
    e_dense = eng(dense)
    router = ServingRouter([e_moe_ep, e_moe, e_dense])
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 200, (n,)).astype(np.int64)
               for n in (5, 7, 4, 6, 3, 8)]
    rids = [router.submit(p, max_new_tokens=4) for p in prompts]
    for _ in range(2):
        router.step()
    lost = sum(1 for k in router._inflight
               if k[0] == e_moe_ep.engine_id)
    assert lost >= 1                 # the kill actually hits live work
    router.mark_unhealthy(e_moe_ep.engine_id)
    out = router.run_to_completion()
    # zero drops: every request finishes its FULL budget somewhere
    assert sorted(out) == sorted(rids)
    assert all(len(out[r]) == 4 for r in rids)
    assert sum(router.finished[r].requeues for r in rids) == lost
    # the dead MoE pool drained leak-free
    c = e_moe_ep.caches[0]
    assert len(c._free) == c.num_blocks


# --- the sorted grouped product (PR 28): the one-chip expert product ---

def _routing(kind, rng, n, k, e):
    """``top_i [n, k]`` int32, k DISTINCT experts a row (as a top-k
    gives them): drawn evenly, all rows on the same k experts, or
    evenly over every expert but the last, which gets no row."""
    if kind == "one_expert":
        return np.tile(np.arange(k, dtype=np.int32), (n, 1))
    pool = e - 1 if kind == "empty_expert" else e
    return np.stack([rng.permutation(pool)[:k] for _ in range(n)]
                    ).astype(np.int32)


@pytest.mark.parametrize("routing",
                         ["balanced", "one_expert", "empty_expert"])
@pytest.mark.parametrize("n,k,e", [(16, 2, 8), (7, 1, 4), (33, 3, 5)])
def test_sorted_product_equals_the_buffer_dispatch(n, k, e, routing):
    """``sorted_expert_swiglu`` (one ``[N*k, D]`` buffer, three
    ``ragged_dot``s) against the form it replaced in the one-chip
    step, ``dispatch_to_buffers`` -> ``grouped_expert_swiglu`` ->
    ``combine_from_buffers`` at dropless capacity: float32, random
    inputs; rows marked not ``valid`` come back 0 and are in no
    ``load``, and ``sum(load)`` is the valid rows x k."""
    import jax.numpy as jnp
    from paddle_tpu.ops.moe_gate import (
        assignment_slots, combine_from_buffers, dispatch_to_buffers,
        grouped_expert_swiglu, sorted_expert_swiglu)
    d, m = 16, 24
    rng = np.random.default_rng(n * 100 + k * 10 + e)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    wg = jnp.asarray(rng.standard_normal((e, d, m)) * 0.3, jnp.float32)
    wu = jnp.asarray(rng.standard_normal((e, d, m)) * 0.3, jnp.float32)
    wd = jnp.asarray(rng.standard_normal((e, m, d)) * 0.3, jnp.float32)
    top_i = jnp.asarray(_routing(routing, rng, n, k, e))
    top_w = jnp.asarray(rng.random((n, k)) + 0.1, jnp.float32)

    slot, _ = assignment_slots(top_i, e)
    disp = dispatch_to_buffers(x, top_i, slot, None, e, n * k)
    want = np.asarray(combine_from_buffers(
        grouped_expert_swiglu(disp, wg, wu, wd), top_i, slot, top_w))

    out, load = sorted_expert_swiglu(x, top_i, top_w, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5,
                               atol=2e-5)
    counts = np.bincount(np.asarray(top_i).reshape(-1), minlength=e)
    assert np.asarray(load).tolist() == counts.tolist()
    if routing == "one_expert":
        assert counts[:k].tolist() == [n] * k and not counts[k:].any()
    if routing == "empty_expert":
        assert counts[-1] == 0

    valid = rng.random(n) < 0.6
    valid[0], valid[-1] = True, False
    out_v, load_v = sorted_expert_swiglu(x, top_i, top_w, wg, wu, wd,
                                         valid=jnp.asarray(valid))
    out_v = np.asarray(out_v)
    assert not out_v[~valid].any()
    np.testing.assert_allclose(out_v[valid], want[valid], rtol=2e-5,
                               atol=2e-5)
    assert np.asarray(load_v).tolist() == np.bincount(
        np.asarray(top_i)[valid].reshape(-1), minlength=e).tolist()
    assert int(np.asarray(load_v).sum()) == int(valid.sum()) * k


# the Pallas grouped product (interpret mode): name -> (tokens, k,
# router width, experts held, first held, rows that are tokens or None)
GROUPED_CASES = {
    # tile 128 (256 rows an expert); an expert's rows span tiles
    "wide_experts": (512, 2, 4, 4, 0, None),
    # tile 16; 40 thin experts of a router 160 wide, most rows elsewhere
    "thin_experts": (24, 6, 160, 40, 40, None),
    "empty_expert": (32, 2, 8, 8, 0, None),
    "one_expert": (32, 2, 8, 8, 0, None),
    # 12 rows: not a multiple of 8 (XLA:TPU expands that ragged_dot)
    "twelve_rows": (6, 2, 8, 8, 0, None),
    # experts 8..15 of 32 held: rows before and behind them held elsewhere
    "held_share": (40, 6, 32, 8, 8, None),
    "valid_padding": (32, 2, 8, 8, 0, 19),
}


def _grouped_case(name, dtype):
    import jax.numpy as jnp
    n, k, e_all, e_held, first, n_valid = GROUPED_CASES[name]
    d, m = 128, 256
    rng = np.random.default_rng(sorted(GROUPED_CASES).index(name))
    kind = name if name in ("one_expert", "empty_expert") else "balanced"
    top_i = _routing(kind, rng, n, k, e_all)
    x = jnp.asarray(rng.standard_normal((n, d)), dtype)
    wg, wu = (jnp.asarray(rng.standard_normal((e_held, d, m)) * 0.1, dtype)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((e_held, m, d)) * 0.1, dtype)
    top_w = jnp.asarray(rng.random((n, k)) + 0.1, jnp.float32)
    valid = None if n_valid is None else np.arange(n) < n_valid
    return x, top_i, top_w, wg, wu, wd, first, valid


@pytest.mark.parametrize("against", ["plain_loop_f32", "ragged_dot_bf16"])
@pytest.mark.parametrize("name", sorted(GROUPED_CASES))
def test_grouped_kernel_interpret(name, against):
    """``sorted_expert_swiglu`` on the Pallas grouped product
    (``ops/pallas_kernels.grouped_expert_matmul``, interpret mode: each
    expert's rows on a tile boundary, gate and up one launch) against a
    plain loop over the held experts in float32, and in bf16 against
    the ``ragged_dot`` form it replaces on the TPU; ``load`` equal in
    every case, rows no held expert owns (or that are padding) 0."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.moe_gate import sorted_expert_swiglu
    from paddle_tpu.ops.pallas_kernels import grouped_tile_rows
    f32 = against == "plain_loop_f32"
    x, top_i, top_w, wg, wu, wd, first, valid = _grouped_case(
        name, jnp.float32 if f32 else jnp.bfloat16)
    n, k = top_i.shape
    e_held = wg.shape[0]
    assert grouped_tile_rows(n * k, e_held) == (
        128 if name == "wide_experts" else 16)
    args = (x, jnp.asarray(top_i), top_w, wg, wu, wd, first,
            None if valid is None else jnp.asarray(valid))
    with jax.enable_x64(False):
        out, load = sorted_expert_swiglu(*args, interpret=True)
        ref, ref_load = sorted_expert_swiglu(*args, use_pallas=False)
    out = np.asarray(out.astype(jnp.float32))
    local = top_i - first
    held = (local >= 0) & (local < e_held)
    if valid is not None:
        held &= valid[:, None]
    counts = np.bincount(local[held], minlength=e_held)
    assert np.asarray(load).tolist() == counts.tolist() \
        == np.asarray(ref_load).tolist()
    if name == "empty_expert":
        assert counts[-1] == 0
    if name == "one_expert":
        assert counts[:k].tolist() == [n] * k and not counts[k:].any()
    if name in ("held_share", "thin_experts"):
        assert (local < 0).any() and (local >= e_held).any()
    assert not out[~held.any(axis=1)].any()
    if f32:
        xs = np.asarray(x, np.float64)
        g, u, dn = (np.asarray(w, np.float64) for w in (wg, wu, wd))
        want = np.zeros((n, x.shape[1]))
        for t, j in zip(*np.nonzero(held)):
            e = local[t, j]
            a = xs[t] @ g[e]
            want[t] += float(top_w[t, j]) * (
                (a / (1 + np.exp(-a)) * (xs[t] @ u[e])) @ dn[e])
        np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)
    else:
        ref = np.asarray(ref.astype(jnp.float32))
        # bf16 against bf16: the kernel keeps g and u in float32 up to
        # the one cast, the ragged_dot form rounds each to bf16 first
        np.testing.assert_allclose(out, ref, rtol=0.05,
                                   atol=0.02 * np.abs(ref).max())


def test_grouped_slot_tables_skip_experts_without_rows():
    """The launch's scalar tables: the experts that have rows first, in
    order, each at the tile-aligned row its rows start at; the slots
    behind them repeat the last such expert with no tile (same weight
    block, nothing fetched); a step no held expert was given a row in
    has no live slot."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import (grouped_buffer_rows,
                                               grouped_slot_tables)
    expert, first, tiles = (np.asarray(t) for t in grouped_slot_tables(
        jnp.asarray([0, 17, 0, 16, 1, 0], jnp.int32), 16))
    assert expert.tolist() == [1, 3, 4, 4, 4, 4]
    assert first.tolist() == [0, 32, 48, 48, 48, 48]
    assert tiles.tolist() == [2, 1, 1, 0, 0, 0]
    assert grouped_buffer_rows(34, 6, 16) >= 64
    _, _, tiles = grouped_slot_tables(jnp.zeros((4,), jnp.int32), 16)
    assert not np.asarray(tiles).any()


def test_moe_ffn_one_chip_is_the_gate_and_the_sorted_product():
    """``moe_ffn`` on one chip: ITS gate (``topk_gate``, renormalised
    over the k) and then the product the held path calls, with the
    bank at the router's width; it returns the experts' loads, and a
    bank narrower than its router is still refused."""
    import jax.numpy as jnp
    from paddle_tpu.ops.moe_gate import (moe_ffn, sorted_expert_swiglu,
                                         topk_gate)
    n, d, m, e, k = 12, 16, 24, 4, 2
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    gw = jnp.asarray(rng.standard_normal((d, e)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((e, d, m)) * 0.3,
                          jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((e, m, d)) * 0.3, jnp.float32)
    valid = jnp.asarray(np.arange(n) < 9)
    out, load = moe_ffn(x, gw, wg, wu, wd, top_k=k, valid=valid)
    top_w, top_i, _ = topk_gate(x @ gw, k)
    np.testing.assert_allclose(np.asarray(top_w).sum(-1), 1.0, rtol=1e-6)
    want, want_load = sorted_expert_swiglu(x, top_i, top_w, wg, wu, wd,
                                           valid=valid)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    assert np.asarray(load).tolist() == np.asarray(want_load).tolist()
    assert int(np.asarray(load).sum()) == 9 * k
    with pytest.raises(ValueError, match="moe_ffn_held"):
        moe_ffn(x, gw, wg[:2], wu[:2], wd[:2], top_k=k)


def test_one_chip_step_counts_its_experts_rows():
    """The one-chip Mixtral engine fills what only the held-share path
    filled: ``moe_rows`` in every step record and
    ``serving_moe_expert_load_total{expert}``, real tokens only; the
    ep engine's step (the buffer exchange) counts nothing."""
    from paddle_tpu.observability import default_registry, span_log
    from paddle_tpu.inference.serving import STEP_SPAN
    model = _model()
    cfg = model.config

    def counted():
        fam = default_registry().get("serving_moe_expert_load_total")
        return 0.0 if fam is None else sum(
            fam.labels(expert=str(e)).value
            for e in range(cfg.num_local_experts))

    before = counted()
    eng, _ = _run(model)
    assert eng.mixed.n_stats == 3 + cfg.num_local_experts
    recs = [e[5] for e in span_log.events()
            if e[1] == STEP_SPAN and e[5]["engine"] == eng.engine_id]
    per_token = cfg.num_experts_per_tok * cfg.num_hidden_layers
    assert recs and all(f["moe_rows"] == f["tokens"] * per_token
                        for f in recs)
    assert counted() - before == sum(f["moe_rows"] for f in recs)
    e_ep, _ = _run(model, mesh=ep_mesh(2))
    assert e_ep.mixed.n_stats == 0
