"""Quantized serving (round 13): int8 paged KV cache, int8 PTQ
weights, quantized tp collectives.

Tier-1 (fast, ~5s in-suite): int8-KV mixed-step token match vs the
fp32 engine + honest capacity accounting, scale-carrying COW +
refcount audit at the PagedKVCache level, construction-time rejection
of unsupported combos, and the one-symmetric-absmax-helper contract.
Everything engine-heavy beyond that (w8 end-to-end, tp=2 quantized
collectives, write-path sweeps, PTQ round trip) is slow-lane — the
870s tier-1 budget is hard.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.inference.serving import ContinuousBatchingEngine


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama_tiny_config()
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return cfg, model


def _run_engine(model, prompts, budgets, **kw):
    eng = ContinuousBatchingEngine(model, max_batch_size=4,
                                   num_blocks=64, block_size=4, **kw)
    rids = []
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        rids.append(eng.add_request(p, b))
        if i % 2 == 0:
            eng.step()              # staggered admission (churn)
    eng.run_to_completion()
    return [eng.result(r) for r in rids], eng


def _match_rate(ref, got):
    tot = sum(len(a) for a in ref)
    hit = sum(x == y for a, b in zip(ref, got) for x, y in zip(a, b))
    return hit / max(1, tot), tot - hit


def test_kv8_mixed_token_match_and_capacity(tiny_model):
    """int8-KV mixed engine vs fp32 on a staggered mix: token-match
    rate over the tolerance threshold, compile bound intact, pool
    bytes ≥1.9× denser WITH scales counted, gauge reports 8 bits."""
    cfg, model = tiny_model
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab_size, (n,)).astype(np.int64)
               for n in (5, 3, 8)]
    budgets = [6, 8, 5]
    kw = dict(prefill_chunk_size=8)
    ref, ef = _run_engine(model, prompts, budgets, **kw)
    got, eq = _run_engine(model, prompts, budgets, kv_dtype="int8",
                          **kw)
    rate, mismatches = _match_rate(ref, got)
    eq.record_token_mismatches(mismatches)
    assert rate >= 0.6, f"kv8 token-match rate {rate} below threshold"
    assert eq.mixed.total_compiles <= len(eq.token_budgets)
    # capacity: scales included, still ≥1.9× pages per HBM byte
    fp_bytes = ef.caches[0].per_chip_pool_bytes()
    q_bytes = eq.caches[0].per_chip_pool_bytes()
    assert fp_bytes / q_bytes >= 1.9
    c = eq.caches[0]
    phys = c.num_blocks + 1
    bs, hkv, d = c.block_size, c.num_kv_heads, c.head_dim
    assert q_bytes == 2 * phys * bs * hkv * d + 2 * phys * hkv * 4
    from paddle_tpu.observability import default_registry
    assert default_registry().get(
        "serving_kv_quant_dtype").value == 8.0


def test_kv8_cow_carries_scales_and_refcounts():
    """COW copy_block must move a page's absmax row with its codes
    (a reader of the copy dequantizes identically), and the refcounted
    release path must stay leak-free with scale tables attached."""
    import jax.numpy as jnp
    from paddle_tpu.jit.serving_step import copy_block
    from paddle_tpu.ops.paged_attention import (PagedKVCache,
                                                dequant_pages,
                                                write_ragged_kv_q8)
    rng = np.random.RandomState(0)
    bs, hkv, d = 4, 2, 8
    caches = [PagedKVCache(8, bs, hkv, d, sink_block=True,
                           kv_dtype="int8") for _ in range(2)]
    src = caches[0].allocate_block()
    for c in caches:                    # one full page per layer
        k = rng.randn(bs, hkv, d).astype(np.float32)
        v = rng.randn(bs, hkv, d).astype(np.float32)
        blks = np.full((bs,), src, np.int32)
        offs = np.arange(bs, dtype=np.int32)
        c.key_cache, c.value_cache, c.key_scale, c.value_scale = \
            write_ragged_kv_q8(jnp.asarray(k), jnp.asarray(v),
                               c.key_cache, c.value_cache,
                               c.key_scale, c.value_scale, blks, offs)
    dst = caches[0].allocate_block()
    copy_block(caches, src, dst)
    for c in caches:
        np.testing.assert_array_equal(np.asarray(c.key_scale[dst]),
                                      np.asarray(c.key_scale[src]))
        np.testing.assert_array_equal(
            np.asarray(dequant_pages(c.key_cache[dst],
                                     c.key_scale[dst])),
            np.asarray(dequant_pages(c.key_cache[src],
                                     c.key_scale[src])))
    # refcount audit: share, then release through the single path
    c0 = caches[0]
    c0.share_blocks([src])
    c0.free_sequence([src])
    assert c0.refcount(src) == 1        # survived the shared drop
    c0.free_sequence([src, dst])
    assert c0.refcount(src) == 0 and c0.refcount(dst) == 0
    assert sorted(c0._free) == list(range(c0.num_blocks))


def test_quant_construction_errors(tiny_model):
    """PR-7 norm: unsupported combos die at engine construction with a
    clear message, not inside tracing."""
    _cfg, model = tiny_model
    base = dict(max_batch_size=4, num_blocks=64, block_size=4)
    with pytest.raises(ValueError, match="kv_dtype"):
        ContinuousBatchingEngine(model, kv_dtype="int4",
                                 **base)
    with pytest.raises(ValueError, match="weight_quant"):
        ContinuousBatchingEngine(model, weight_quant="fp8",
                                 **base)
    with pytest.raises(ValueError, match="single-chip"):
        ContinuousBatchingEngine(model, quant_collectives=True,
                                 **base)


def test_one_symmetric_absmax_helper():
    """Satellite contract: QAT fake-quant and the serving PTQ path
    share ONE clamp implementation (quantization.functional)."""
    import jax.numpy as jnp
    from paddle_tpu.quantization import _fake_quant
    from paddle_tpu.quantization.functional import (
        dequantize_symmetric, fake_quantize, quantize_symmetric)
    from paddle_tpu.core.tensor import Tensor
    rng = np.random.RandomState(3)
    x = rng.randn(6, 5).astype(np.float32) * 3
    s = np.abs(x).max()
    want = np.asarray(fake_quantize(jnp.asarray(x), s))
    np.testing.assert_allclose(
        np.asarray(dequantize_symmetric(
            quantize_symmetric(jnp.asarray(x), s), s)), want)
    got = np.asarray(_fake_quant(Tensor(x), s)._value)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # codes clip symmetrically: -128 never appears
    codes = np.asarray(quantize_symmetric(jnp.asarray(x * 100), s))
    assert codes.min() >= -127 and codes.max() <= 127
    # the Pallas kernels' in-kernel static constant tracks the helper
    from paddle_tpu.ops.paged_attention import _KV_BNT
    from paddle_tpu.quantization.functional import symmetric_bound
    assert _KV_BNT == symmetric_bound(8)


# ---------------------------------------------------------------------------
# slow lane
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_w8_kv8_prefix_cow_end_to_end(tiny_model):
    """Full quant config (int8 KV + int8 weights) with prefix caching:
    token match vs fp32, a real prefix hit (COW rides the quantized
    pool), and the pool leak-free after completion."""
    cfg, model = tiny_model
    rng = np.random.RandomState(11)
    P = rng.randint(1, cfg.vocab_size, (12,)).astype(np.int64)
    prompts = [np.concatenate([P, rng.randint(1, cfg.vocab_size,
                                              (4,)).astype(np.int64)])
               for _ in range(3)]
    budgets = [5, 5, 5]
    kw = dict(prefill_chunk_size=8,
              enable_prefix_cache=True)

    def run(**extra):
        eng = ContinuousBatchingEngine(model, max_batch_size=4,
                                       num_blocks=64, block_size=4,
                                       **kw, **extra)
        # first request publishes the shared prefix's pages; the
        # laggards admit against a warm table (a real hit + COW)
        r0 = eng.add_request(prompts[0], budgets[0])
        eng.run_to_completion()
        rest = [eng.add_request(p, b)
                for p, b in zip(prompts[1:], budgets[1:])]
        eng.run_to_completion()
        return [eng.result(r) for r in [r0] + rest], eng

    ref, ef = run()
    got, eq = run(kv_dtype="int8", weight_quant="int8")
    rate, mismatches = _match_rate(ref, got)
    eq.record_token_mismatches(mismatches)
    assert rate >= 0.6, f"kv8+w8 token-match rate {rate}"
    assert eq.prefix_cache.hits >= 1          # sharing really happened
    c = eq.caches[0]
    assert len(c._free) + len(eq.prefix_cache.cached_blocks()) \
        == c.num_blocks


@pytest.mark.slow
def test_tp2_quant_collective_token_match(tiny_model):
    """tp=2 with the EQuARX-style int8 logits all-gather: tokens match
    the single-chip fp32 engine within tolerance; quantized collective
    bytes are accounted (int8 codes + 4-byte scale per shard)."""
    from paddle_tpu.jit.spmd import tp_mesh
    cfg0, _ = tiny_model
    cfg = llama_tiny_config(num_key_value_heads=4)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab_size, (n,)).astype(np.int64)
               for n in (5, 3, 8)]
    budgets = [6, 8, 5]
    kw = dict(prefill_chunk_size=8)
    ref, _ = _run_engine(model, prompts, budgets, **kw)
    got, eng = _run_engine(model, prompts, budgets, mesh=tp_mesh(2),
                           kv_dtype="int8", quant_collectives=True,
                           **kw)
    rate, mismatches = _match_rate(ref, got)
    eng.record_token_mismatches(mismatches)
    assert rate >= 0.6, f"tp2 quant-collective token-match rate {rate}"
    by_op = eng.mixed.collective_bytes(eng.token_budgets[-1])
    assert by_op["all_gather"] == \
        eng.max_batch_size * (cfg.vocab_size // 2) + 4
    from paddle_tpu.observability import default_registry
    assert default_registry().get(
        "serving_quant_collective_bytes_total").labels(
        op="all_gather").value > 0


@pytest.mark.slow
def test_quant_write_paths_match_fp32_within_bound():
    """Per-page scale correctness sweep: the ragged quantized write
    (interleaved spans, then one token a page with growing magnitudes)
    lands within the absmax/127 quantization step of what the fp32
    write stores (plus rescale slack)."""
    import jax.numpy as jnp
    from paddle_tpu.ops.paged_attention import (
        PagedKVCache, dequant_pages, write_ragged_kv, write_ragged_kv_q8)
    rng = np.random.RandomState(5)
    bs, hkv, d = 4, 2, 8

    def pair():
        return (PagedKVCache(8, bs, hkv, d, sink_block=True),
                PagedKVCache(8, bs, hkv, d, sink_block=True,
                             kv_dtype="int8"))

    def check(cf, cq, pages):
        deq = np.asarray(dequant_pages(cq.key_cache, cq.key_scale))
        ref = np.asarray(cf.key_cache)
        for p in pages:
            bound = 2.0 * max(float(np.asarray(cq.key_scale)[p].max()),
                              1e-9) / 127.0
            assert np.abs(deq[p] - ref[p]).max() <= bound

    # ragged: interleaved spans over two pages, three writes
    cf, cq = pair()
    for _ in range(3):
        n = 5
        k = rng.randn(n, hkv, d).astype(np.float32)
        v = rng.randn(n, hkv, d).astype(np.float32)
        blks = rng.randint(0, 2, (n,)).astype(np.int32)
        offs = np.arange(n, dtype=np.int32) % bs
        cf.key_cache, cf.value_cache = write_ragged_kv(
            jnp.asarray(k), jnp.asarray(v), cf.key_cache,
            cf.value_cache, blks, offs)
        (cq.key_cache, cq.value_cache, cq.key_scale,
         cq.value_scale) = write_ragged_kv_q8(
            jnp.asarray(k), jnp.asarray(v), cq.key_cache,
            cq.value_cache, cq.key_scale, cq.value_scale, blks, offs)
    check(cf, cq, [0, 1])

    # the quantized Pallas ragged + decode kernels (interpret mode)
    # agree with the dequantizing XLA references: the legacy
    # (pipelined=False) decode kernel keeps the r13 dequant math and
    # stays within 1e-5; the int8-MXU kernels additionally quantize
    # the q rows in-kernel and are gated at the DECLARED tolerance
    # (KERNEL_INT8_REL_TOL of the pool's dequantized magnitude)
    from paddle_tpu.ops.paged_attention import (KERNEL_INT8_REL_TOL,
                                                paged_attention,
                                                ragged_paged_attention)
    rng2 = np.random.RandomState(9)
    q = rng2.randn(6, 4, d).astype(np.float32)
    bt2 = np.full((2, 4), cq.sink, np.int32)
    bt2[0, :2] = [0, 1]
    bt2[1, :2] = [0, 1]
    qo = np.array([0, 5], np.int32)
    ql = np.array([5, 1], np.int32)
    kl = np.array([7, 8], np.int32)
    vmag = float(np.abs(np.asarray(
        dequant_pages(cq.value_cache, cq.value_scale))).max())
    o_ref = np.asarray(ragged_paged_attention(
        jnp.asarray(q), cq.key_cache, cq.value_cache, bt2, qo, ql, kl,
        use_pallas=False, key_scale=cq.key_scale,
        value_scale=cq.value_scale))
    o_pal = np.asarray(ragged_paged_attention(
        jnp.asarray(q), cq.key_cache, cq.value_cache, bt2, qo, ql,
        kl, interpret=True, key_scale=cq.key_scale,
        value_scale=cq.value_scale))
    np.testing.assert_allclose(o_pal, o_ref,
                               atol=KERNEL_INT8_REL_TOL * vmag)
    sl = np.array([7, 5], np.int32)
    d_ref = np.asarray(paged_attention(
        jnp.asarray(q[:2]), cq.key_cache, cq.value_cache, bt2, sl,
        use_pallas=False, key_scale=cq.key_scale,
        value_scale=cq.value_scale))
    for pipelined, atol in ((False, 1e-5),
                            (True, KERNEL_INT8_REL_TOL * vmag)):
        d_pal = np.asarray(paged_attention(
            jnp.asarray(q[:2]), cq.key_cache, cq.value_cache, bt2, sl,
            interpret=True, key_scale=cq.key_scale,
            value_scale=cq.value_scale, pipelined=pipelined))
        np.testing.assert_allclose(d_pal, d_ref, atol=atol)

    # one token per page per write, running-max rescale over bs steps
    cf, cq = pair()
    blks = np.array([4, 5], np.int32)
    for step in range(bs):
        k = (rng.randn(2, hkv, d) * (1 + step)).astype(np.float32)
        v = rng.randn(2, hkv, d).astype(np.float32)
        offs = np.full((2,), step, np.int32)
        cf.key_cache, cf.value_cache = write_ragged_kv(
            jnp.asarray(k), jnp.asarray(v), cf.key_cache,
            cf.value_cache, blks, offs)
        (cq.key_cache, cq.value_cache, cq.key_scale,
         cq.value_scale) = write_ragged_kv_q8(
            jnp.asarray(k), jnp.asarray(v), cq.key_cache,
            cq.value_cache, cq.key_scale, cq.value_scale, blks, offs)
    # growing magnitudes force repeated rescales: allow 2 quant steps
    check(cf, cq, [4, 5])


@pytest.mark.slow
def test_ptq_weight_roundtrip_and_tp_specs(tiny_model):
    """quantize_param_tree: per-output-channel error bound, scale keys
    classified into the right tp PartitionSpecs, dequant tree restores
    every key bind_state expects."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.jit.spmd import SpecLayout, llama_param_specs
    from paddle_tpu.quantization.functional import (
        WEIGHT_SCALE_SUFFIX, dequantize_param_tree, quantize_param_tree)
    _cfg, model = tiny_model
    vals = {k: t._value for k, t in model.state_dict().items()}
    qtree = quantize_param_tree(vals)
    scale_keys = [k for k in qtree if k.endswith(WEIGHT_SCALE_SUFFIX)]
    assert scale_keys, "no weights were quantized"
    for sk in scale_keys:
        base = sk[: -len(WEIGHT_SCALE_SUFFIX)]
        assert qtree[base].dtype == jnp.int8
        w = np.asarray(vals[base], np.float32)
        s = np.asarray(qtree[sk])
        deq = np.asarray(qtree[base], np.float32) * s[None, :] / 127.0
        # per-channel error ≤ half a quantization step (+ fp slack)
        assert np.abs(deq - w).max(axis=0).max() <= \
            (s / 127.0 * 0.5 + 1e-6).max()
        assert s.shape == (w.shape[1],)
    # embeddings/norms pass through untouched
    emb = [k for k in vals if "embed_tokens" in k][0]
    assert qtree[emb] is vals[emb]
    # spec classification: col-sharded scales shard, row-sharded don't
    specs = llama_param_specs(qtree.keys(), SpecLayout())
    for sk in scale_keys:
        base = sk[: -len(WEIGHT_SCALE_SUFFIX)]
        if any(f in base for f in ("q_proj", "k_proj", "v_proj",
                                   "gate_proj", "up_proj", "lm_head")):
            assert specs[sk] == P("tp"), sk
        else:
            assert specs[sk] == P(), sk
        assert specs[base] == llama_param_specs([base],
                                                SpecLayout())[base]
    deq_tree = dequantize_param_tree(qtree, jnp.float32)
    assert set(deq_tree) == set(vals)


# ---------------------------------------------------------------------------
# round 17: int8 MXU kernel path (q quantized in-kernel, scale-folded
# scores) — interpret-vs-XLA-reference parity at the DECLARED tolerance
# ---------------------------------------------------------------------------
def _q8_pool(nb, bs, hkv, d, rounds, mag_growth, rng_, seed_cache=None):
    """An int8 pool filled through the real quantize-on-write path,
    with per-round magnitude growth to force running-absmax rescales
    of existing codes (the r13 'growing-magnitude' regime)."""
    import jax.numpy as jnp
    from paddle_tpu.ops.paged_attention import (PagedKVCache,
                                                write_ragged_kv_q8)
    cq = seed_cache or PagedKVCache(nb, bs, hkv, d, sink_block=True,
                                    kv_dtype="int8")
    for r in range(rounds):
        n = bs * (nb // 2)
        mag = mag_growth ** r
        k = (rng_.randn(n, hkv, d) * mag).astype(np.float32)
        v = (rng_.randn(n, hkv, d) * mag).astype(np.float32)
        blks = np.repeat(np.arange(nb // 2, dtype=np.int32), bs)
        offs = np.tile(np.arange(bs, dtype=np.int32), nb // 2)
        (cq.key_cache, cq.value_cache, cq.key_scale,
         cq.value_scale) = write_ragged_kv_q8(
            jnp.asarray(k), jnp.asarray(v), cq.key_cache,
            cq.value_cache, cq.key_scale, cq.value_scale,
            jnp.asarray(blks), jnp.asarray(offs))
    return cq


def _int8_parity_case(cq, spans, W, H, d, rng_):
    """One interpret-mode vs XLA-reference comparison; returns
    (max_abs_err, declared_atol)."""
    import jax.numpy as jnp
    from paddle_tpu.ops.paged_attention import (KERNEL_INT8_REL_TOL,
                                                dequant_pages,
                                                ragged_paged_attention)
    rows = []
    for _q_len, kv_len in spans:
        used = -(-kv_len // cq.block_size)
        tab = np.full((W,), cq.sink, np.int32)
        tab[:used] = np.arange(used, dtype=np.int32) \
            % (cq.num_blocks // 2)
        rows.append(tab)
    bt = np.stack(rows)
    T = sum(q for q, _ in spans)
    q = rng_.randn(T, H, d).astype(np.float32)
    q_offsets = np.cumsum([0] + [q_ for q_, _ in spans[:-1]]) \
        .astype(np.int32)
    q_lens = np.asarray([q_ for q_, _ in spans], np.int32)
    kv_lens = np.asarray([kv for _, kv in spans], np.int32)
    common = (bt, q_offsets, q_lens, kv_lens)
    ref = np.asarray(ragged_paged_attention(
        q, cq.key_cache, cq.value_cache, *common, use_pallas=False,
        key_scale=cq.key_scale, value_scale=cq.value_scale))
    got = np.asarray(ragged_paged_attention(
        q, cq.key_cache, cq.value_cache, *common, interpret=True,
        key_scale=cq.key_scale, value_scale=cq.value_scale))
    vmag = float(np.abs(np.asarray(dequant_pages(
        cq.value_cache, cq.value_scale))).max())
    return float(np.abs(got - ref).max()), KERNEL_INT8_REL_TOL * vmag


@pytest.mark.parametrize("hkv", [2, 4])
def test_int8_mxu_kernel_parity_representative(hkv):
    """Tier-1 representative case (the full sweep is slow-lane): one
    small decode+chunk mix through the int8 MXU ragged kernel stays
    inside the declared tolerance of the dequantizing XLA reference.
    With 4 kv heads a 32-bit sublane of a stored page holds all four
    heads' codes, and the kernel shifts each head's byte out."""
    rng_ = np.random.RandomState(21)
    cq = _q8_pool(nb=8, bs=4, hkv=hkv, d=8, rounds=2, mag_growth=2.0,
                  rng_=rng_)
    err, atol = _int8_parity_case(
        cq, spans=[(1, 7), (4, 8)], W=2, H=2 * hkv, d=8, rng_=rng_)
    assert err <= atol, (err, atol)


@pytest.mark.slow
def test_int8_mxu_kernel_parity_sweep():
    """Declared-tolerance sweep for the int8 MXU path: span shapes ×
    page counts × growing-magnitude rescale histories (each history
    re-quantizes existing codes through the running-absmax path before
    the kernel reads them).  Magnitudes stay inside the declared
    tolerance's validity regime (see KERNEL_INT8_REL_TOL: the q-quant
    perturbation lands in the softmax EXPONENT, so at extreme K
    magnitudes output error amplifies unboundedly — that regime is
    covered by the engine-level token-match gates, not a tensor atol).
    Also pins the decode kernel and the legacy (pipelined=False)
    kernel's tighter 1e-5 bound on one case."""
    import jax.numpy as jnp
    from paddle_tpu.ops.paged_attention import (KERNEL_INT8_REL_TOL,
                                                dequant_pages,
                                                paged_attention)
    for rounds, growth in ((1, 1.0), (3, 2.0), (2, 3.0)):
        rng_ = np.random.RandomState(100 + rounds)
        cq = _q8_pool(nb=16, bs=4, hkv=2, d=16, rounds=rounds,
                      mag_growth=growth, rng_=rng_)
        for spans, W in (
                ([(1, 5), (1, 9), (1, 1), (1, 16)], 4),      # decode
                ([(6, 6), (1, 7), (4, 12)], 4),              # mixed
                ([(8, 16)], 4),                              # aligned
                ([(3, 11), (0, 1), (2, 10)], 8),             # padded
                ([(1, 30), (20, 29), (9, 9)], 8)):   # mid-page, > tile
            err, atol = _int8_parity_case(cq, spans, W, 4, 16, rng_)
            assert err <= atol, (rounds, growth, spans, err, atol)
    # decode kernel, same declared tolerance
    rng_ = np.random.RandomState(7)
    cq = _q8_pool(nb=8, bs=4, hkv=2, d=16, rounds=3, mag_growth=2.0,
                  rng_=rng_)
    q = rng_.randn(2, 4, 16).astype(np.float32)
    bt = np.array([[0, 1], [2, 3]], np.int32)
    sl = np.array([7, 5], np.int32)
    ref = np.asarray(paged_attention(
        q, cq.key_cache, cq.value_cache, bt, sl, use_pallas=False,
        key_scale=cq.key_scale, value_scale=cq.value_scale))
    vmag = float(np.abs(np.asarray(dequant_pages(
        cq.value_cache, cq.value_scale))).max())
    for pipelined, atol in ((True, KERNEL_INT8_REL_TOL * vmag),
                            (False, 1e-5)):
        got = np.asarray(paged_attention(
            q, cq.key_cache, cq.value_cache, bt, sl, interpret=True,
            key_scale=cq.key_scale, value_scale=cq.value_scale,
            pipelined=pipelined))
        assert np.abs(got - ref).max() <= atol
