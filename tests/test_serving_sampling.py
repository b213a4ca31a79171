"""Sampling + speculative decoding in the fused serving steps
(ISSUE round-14 tentpole).

Contracts under test:

- defaults unchanged: engines without ``sampling=``/``draft_model=``
  keep the round-13 pack layout and greedy tokens (byte parity is
  carried by the existing test_serving suites; here we pin the layout
  and the construction-time validation);
- seeded determinism: a sampled request's tokens depend only on
  (seed, position), never on batching, engine flavor, or knob churn —
  and varying knobs/seeds NEVER retraces a module;
- greedy speculative decode is byte-identical to non-speculative
  greedy (CPU-checkable gate), with compile counts bounded and pages
  leak-free;
- statistical shape of the sampled distribution (chi-square) and the
  top-k / top-p supports — slow lane;
- spec-decode interplay with COW prefix sharing, lazy victim
  truncation + page rollback, int8 KV pools, and tensor parallelism —
  slow lane.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle


def _tiny_model(seed=0):
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    paddle.seed(seed)
    cfg = llama_tiny_config(num_hidden_layers=2, hidden_size=64,
                            num_attention_heads=4, num_key_value_heads=2,
                            vocab_size=128, intermediate_size=128)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model


def _ref_tokens(model, prompt, budget):
    out = model.generate(paddle.to_tensor(prompt[None, :]),
                         max_new_tokens=budget)
    return np.asarray(out._value)[0, len(prompt):].tolist()


def test_sampling_defaults_and_validation():
    """Default engines keep the round-13 pack layout (no sampling / no
    n_draft columns) and the new knobs are rejected with actionable
    errors when the engine was not built for them."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                   num_blocks=16, block_size=4,
                                   prefill_chunk_size=4)
    # round-13 span-row layout: block table + exactly 4 descriptors
    assert eng.mixed.row_extra == 4
    pack, _tok, span = eng.mixed.new_pack(eng.token_budgets[0])
    assert span.shape[1] == eng.bt_width + 4
    assert eng.mixed.spec_k == 0 and not eng.mixed.sampling
    # sampling knobs on a greedy engine: construction-time error
    with pytest.raises(ValueError, match="sampling=True"):
        eng.add_request(np.array([1, 2], np.int64), 4, temperature=0.5)
    # spec needs k >= 1
    from paddle_tpu.models.llama import llama_truncated_draft
    draft = llama_truncated_draft(model, 1)
    with pytest.raises(ValueError, match="spec_k"):
        ContinuousBatchingEngine(model, draft_model=draft, spec_k=0)
    # n>1 needs the prefix cache
    with pytest.raises(ValueError, match="enable_prefix_cache"):
        eng.add_request(np.array([1, 2], np.int64), 4, n=2)
    # sampling engine grows the span row by the 4 knob columns only
    eng_s = ContinuousBatchingEngine(model, max_batch_size=2,
                                     num_blocks=16, block_size=4,
                                     prefill_chunk_size=4,
                                     sampling=True)
    assert eng_s.mixed.row_extra == 8


def test_seeded_sampling_determinism_and_compile_bound():
    """Sampled tokens are a function of (seed, position) only: the
    same request replays identically under different admission
    batching; a different seed diverges; greedy (temperature 0)
    requests inside a sampling engine stay byte-identical to eager
    generate; and knob/seed churn never retraces (they are data, not
    shapes)."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    p0 = np.array([7, 9, 2], np.int64)
    p1 = np.array([3, 14, 15, 92, 65], np.int64)

    def build():
        return ContinuousBatchingEngine(
            model, max_batch_size=4, num_blocks=64, block_size=4,
            prefill_chunk_size=4, sampling=True)

    eng = build()
    ra = eng.add_request(p0, 6, temperature=1.0, seed=11)
    rb = eng.add_request(p1, 6, temperature=0.7, top_k=20, top_p=0.9,
                         seed=5)
    rg = eng.add_request(p0, 4)                      # greedy rides along
    eng.run_to_completion()
    a, b = eng.result(ra), eng.result(rb)
    assert eng.result(rg) == _ref_tokens(model, p0, 4)
    compiles = eng.mixed.total_compiles
    assert compiles <= len(eng.token_budgets)

    # same seeds, different admission timing -> identical tokens; and
    # the SAME engine re-serves varying knobs without retracing
    ra2 = eng.add_request(p0, 6, temperature=1.0, seed=11)
    eng.step()
    rb2 = eng.add_request(p1, 6, temperature=0.7, top_k=20, top_p=0.9,
                          seed=5)
    rc2 = eng.add_request(p1, 6, temperature=2.5, top_k=3, seed=99)
    rd = eng.add_request(p0, 6, temperature=1.0, seed=12)
    eng.run_to_completion()
    assert eng.result(ra2) == a
    assert eng.result(rb2) == b
    assert eng.result(rd) != a          # a different seed diverges
    assert eng.result(rc2) != b
    assert eng.mixed.total_compiles == compiles, (
        "sampling params/seeds retraced the mixed step — they must be "
        "traced data")


def test_spec_greedy_byte_parity_compile_bound_leak_free():
    """Greedy speculative decode must be byte-identical to
    non-speculative greedy (which is itself parity-gated vs eager
    generate): staggered admission, a chunked long prompt riding
    along, compile counts of BOTH modules bounded by the one budget
    set, and every page back in the pool."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models.llama import llama_truncated_draft
    model = _tiny_model()
    draft = llama_truncated_draft(model, 1)
    prompts = [np.array([7, 9, 2], np.int64),
               np.array([3, 14, 15, 92, 65], np.int64),
               np.arange(1, 11, dtype=np.int64)]     # 10 -> chunks of 4
    budgets = [6, 5, 4]
    want = [_ref_tokens(model, p, n) for p, n in zip(prompts, budgets)]
    eng = ContinuousBatchingEngine(model, max_batch_size=4,
                                   num_blocks=64, block_size=4,
                                   prefill_chunk_size=4,
                                   draft_model=draft, spec_k=2)
    r0 = eng.add_request(prompts[0], budgets[0])
    eng.step()                           # r0 speculating alone
    r1 = eng.add_request(prompts[1], budgets[1])
    r2 = eng.add_request(prompts[2], budgets[2])
    eng.run_to_completion()              # chunks mirror into the draft
    for rid, w in zip((r0, r1, r2), want):
        assert eng.result(rid) == w, (
            "greedy speculative output diverged from non-speculative "
            "greedy")
    assert eng.mixed.total_compiles <= len(eng.token_budgets)
    assert eng.draft_step.total_compiles <= len(eng.draft_budgets)
    assert set(eng.mixed.compile_counts) <= set(eng.token_budgets)
    assert len(eng.caches[0]._free) == 64
    # draft pools share the page-id space: no allocator of their own
    assert len(eng.draft_caches[0]._free) == 64


@pytest.mark.slow
def test_sampled_distribution_chi_square_topk_topp():
    """Op-level statistics: gumbel sampling over the filtered logits
    matches softmax(l/T) (chi-square), and the top-k / top-p masks
    bound the support exactly."""
    import jax
    from paddle_tpu.ops.sampling import sample_logits
    rng = np.random.RandomState(3)
    V, n = 32, 6000
    logits = rng.randn(V).astype(np.float32) * 1.5
    big = jnp.broadcast_to(jnp.asarray(logits), (n, V))
    seeds = jnp.full((n,), 17, jnp.int32)
    ctrs = jnp.arange(n, dtype=jnp.int32)
    zi, zf = jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.float32)

    for T in (0.8, 1.0, 1.6):
        temps = jnp.full((n,), T, jnp.float32)
        toks = jax.jit(sample_logits)(big, temps, zi, zf, seeds, ctrs)
        emp = np.bincount(np.asarray(toks), minlength=V) / n
        want = np.asarray(jax.nn.softmax(jnp.asarray(logits) / T))
        chi2 = float(np.sum((emp - want) ** 2
                            / np.maximum(want, 1e-12)) * n)
        # df = V-1 = 31; p=0.999 cutoff ~= 61.1 — a loose, seeded gate
        assert chi2 < 65, (T, chi2)

    temps = jnp.full((n,), 1.0, jnp.float32)
    # top-k support
    toks = jax.jit(sample_logits)(
        big, temps, jnp.full((n,), 4, jnp.int32), zf, seeds, ctrs)
    top4 = set(np.argsort(logits)[-4:].tolist())
    assert set(np.asarray(toks).tolist()) <= top4
    # top-p support: smallest prefix of the sorted probs with mass>=p
    p = 0.6
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits)))
    order = np.argsort(-probs)
    keep = order[: int(np.searchsorted(np.cumsum(probs[order]), p) + 1)]
    toks = jax.jit(sample_logits)(
        big, temps, zi, jnp.full((n,), p, jnp.float32), seeds, ctrs)
    assert set(np.asarray(toks).tolist()) <= set(keep.tolist())
    # the whole nucleus is actually reachable
    assert set(np.asarray(toks).tolist()) == set(keep.tolist())


@pytest.mark.slow
def test_spec_sampled_e2e_cow_truncation_quant():
    """Speculative + sampled end-to-end across the engine's hard
    paths: COW prefix sharing (deterministic replay + refcount audit),
    lazy pool-dry victim truncation with page rollback, and an int8 KV
    target pool (runs, deterministic, leak-free)."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models.llama import llama_truncated_draft
    model = _tiny_model()
    draft = llama_truncated_draft(model, 1)
    P = np.array([5, 17, 42, 7, 99, 3, 11, 23], np.int64)

    def spec_engine(**kw):
        base = dict(max_batch_size=2, num_blocks=32, block_size=4,
                    prefill_chunk_size=4,
                    sampling=True, draft_model=draft, spec_k=2)
        base.update(kw)
        return ContinuousBatchingEngine(model, **base)

    # COW + determinism: the sampled whole-prompt hit replays the same
    # tokens as a cold run with the same seed (sampling depends on
    # positions, not on how the prefix KV was produced)
    eng = spec_engine(enable_prefix_cache=True)
    ra = eng.add_request(P, 6, temperature=1.1, seed=21)
    eng.run_to_completion()
    a = eng.result(ra)
    rc = eng.add_request(P, 6, temperature=1.1, seed=21)   # COW hit
    eng.run_to_completion()
    assert eng.result(rc) == a
    assert eng.finished[rc].prefix_hit_tokens == 7
    c0 = eng.caches[0]
    cached = eng.prefix_cache.cached_blocks()
    assert all(c0.refcount(b) == 1 for b in cached)
    assert len(c0._free) + len(cached) == c0.num_blocks

    # lazy pool-dry: victim truncated, every page rolled back
    eng = spec_engine(num_blocks=4, max_seq_len=32, lazy_alloc=True)
    r0 = eng.add_request(np.array([1, 2, 3], np.int64), 12,
                         temperature=0.9, seed=1)
    r1 = eng.add_request(np.array([4, 5, 6], np.int64), 12,
                         temperature=0.9, seed=2)
    eng.run_to_completion()
    reqs = [eng.finished[r] for r in (r0, r1)]
    assert any(r.truncated for r in reqs)
    for r in reqs:
        assert 0 < len(r.output_ids) <= 12
    assert len(eng.caches[0]._free) == 4

    # int8 KV pools under speculation: deterministic + leak-free
    outs = []
    for _ in range(2):
        eng = spec_engine(kv_dtype="int8")
        rq = eng.add_request(P, 8, temperature=0.8, top_p=0.95, seed=4)
        eng.run_to_completion()
        outs.append(eng.result(rq))
        assert len(eng.caches[0]._free) == 32
    assert outs[0] == outs[1]


@pytest.mark.slow
def test_add_request_n_shares_one_prefill():
    """n>1 generations: ONE prefill, children admit as whole-prompt
    hits against the parent's published pages (ref++ / COW), sampled
    suffixes diverge by seed offset, greedy children are identical,
    and nothing leaks."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    P = np.array([5, 17, 42, 7, 99, 3, 11, 23], np.int64)
    eng = ContinuousBatchingEngine(model, max_batch_size=4,
                                   num_blocks=64, block_size=4,
                                   prefill_chunk_size=4,
                                   sampling=True,
                                   enable_prefix_cache=True)
    rids = eng.add_request(P, 6, temperature=1.4, seed=3, n=3)
    assert isinstance(rids, list) and len(rids) == 3
    eng.run_to_completion()
    outs = [eng.result(r) for r in rids]
    assert len({tuple(o) for o in outs}) > 1, "children must diverge"
    # children shared the parent's prefix pages (7 = whole-prompt hit
    # capped one token short for the COW re-sample)
    for rid in rids[1:]:
        assert eng.finished[rid].prefix_hit_tokens == 7
    pc = eng.prefix_cache
    assert pc.hits >= 2
    c0 = eng.caches[0]
    cached = pc.cached_blocks()
    assert all(c0.refcount(b) == 1 for b in cached)
    assert len(c0._free) + len(cached) == c0.num_blocks
    # greedy n>1 degenerates to identical outputs (documented)
    g = eng.add_request(P, 4, n=2)
    eng.run_to_completion()
    assert eng.result(g[0]) == eng.result(g[1]) \
        == _ref_tokens(model, P, 4)
    # seed replay: generation i of a fresh engine with seed+i matches
    eng2 = ContinuousBatchingEngine(model, max_batch_size=4,
                                    num_blocks=64, block_size=4,
                                    prefill_chunk_size=4, sampling=True,
                                    enable_prefix_cache=True)
    solo = eng2.add_request(P, 6, temperature=1.4, seed=4)  # = seed 3+1
    eng2.run_to_completion()
    assert eng2.result(solo) == outs[1]


@pytest.mark.slow
def test_sampled_parity_single_chip_vs_tp():
    """One sampled request must produce byte-identical tokens through
    the single-chip engine, the same engine at another chunk size, and
    the tp=2 engine (exact logits all-gather + replicated threefry):
    sampling is a function of (seed, position), not of the execution
    plan."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.jit.spmd import tp_mesh
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    paddle.seed(0)
    cfg = llama_tiny_config(num_key_value_heads=4)   # tp=2 divisibility
    model = LlamaForCausalLM(cfg)
    model.eval()
    p = np.array([3, 14, 15, 92, 65], np.int64)

    def run(**kw):
        eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                       num_blocks=32, block_size=4,
                                       sampling=True, **kw)
        rid = eng.add_request(p, 7, temperature=0.9, top_k=50, seed=13)
        eng.run_to_completion()
        return eng.result(rid)

    mixed = run(prefill_chunk_size=4)
    assert run(prefill_chunk_size=8) == mixed
    tp = run(prefill_chunk_size=4, mesh=tp_mesh(2))
    assert tp == mixed, (
        "tp sampling must be byte-identical: the epilogue runs on "
        "replicated post-gather logits")
