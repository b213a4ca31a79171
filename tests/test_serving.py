"""Serving slice: paged KV cache + paged/block/masked attention kernels,
inference Predictor, llama KV-cache generation, continuous batching.

Parity targets: paddle/phi/kernels/fusion/block_multihead_attention_kernel.cu,
masked_multihead_attention, paddle/fluid/inference/api/analysis_predictor.h
(:210 — the scheduler around the predictor).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.paged_attention import (
    PagedKVCache, paged_attention, ragged_paged_attention,
    write_kv_to_cache, reconstruct_kv,
    block_multihead_attention, masked_multihead_attention,
    _paged_attention_xla, _paged_attention_pallas)

rng = np.random.RandomState(0)


def _dense_ref(q, k, v, seq_lens):
    """q [B,H,D], k/v [B,L,Hkv,D] padded; full softmax over valid cols."""
    B, H, D = q.shape
    Hkv = k.shape[2]
    if Hkv != H:
        rep = H // Hkv
        k = np.repeat(k, rep, axis=2)
        v = np.repeat(v, rep, axis=2)
    s = np.einsum("bhd,blhd->bhl", q / np.sqrt(D), k)
    for b, L in enumerate(seq_lens):
        s[b, :, L:] = -np.inf
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhl,blhd->bhd", p, v)


def _build_cache(B, lens, bs=4, Hkv=2, D=8, num_blocks=32):
    cache = PagedKVCache(num_blocks, bs, Hkv, D)
    bt = cache.build_block_table(lens)
    max_len = bt.shape[1] * bs
    k_dense = rng.randn(B, max_len, Hkv, D).astype(np.float32)
    v_dense = rng.randn(B, max_len, Hkv, D).astype(np.float32)
    kc, vc = cache.key_cache, cache.value_cache
    # write token-by-token through the public scatter API
    for s in range(max(lens)):
        write_mask = [s < L for L in lens]
        kc, vc = write_kv_to_cache(
            k_dense[:, s], v_dense[:, s], kc, vc, bt,
            np.asarray([s] * B, np.int32))
        del write_mask   # all writes land; invalid cols masked by seq_lens
    return cache, kc, vc, bt, k_dense, v_dense


def test_cache_write_and_reconstruct():
    lens = [6, 3]
    cache, kc, vc, bt, k_dense, v_dense = _build_cache(2, lens)
    k_back, v_back = reconstruct_kv(kc, vc, bt, max_len=8)
    for b, L in enumerate(lens):
        np.testing.assert_allclose(np.asarray(k_back)[b, :L],
                                   k_dense[b, :L], rtol=1e-6)
        np.testing.assert_allclose(np.asarray(v_back)[b, :L],
                                   v_dense[b, :L], rtol=1e-6)


@pytest.mark.parametrize("H,Hkv", [(2, 2), (4, 2)])
def test_paged_attention_matches_dense(H, Hkv):
    lens = [7, 3]
    B, D = 2, 8
    cache = PagedKVCache(16, 4, Hkv, D)
    bt = cache.build_block_table(lens)
    kc, vc = cache.key_cache, cache.value_cache
    max_len = bt.shape[1] * 4
    k_dense = rng.randn(B, max_len, Hkv, D).astype(np.float32)
    v_dense = rng.randn(B, max_len, Hkv, D).astype(np.float32)
    for s in range(max(lens)):
        kc, vc = write_kv_to_cache(k_dense[:, s], v_dense[:, s], kc, vc,
                                   bt, np.asarray([s] * B, np.int32))
    q = rng.randn(B, H, D).astype(np.float32)
    got = paged_attention(q, kc, vc, bt, np.asarray(lens, np.int32),
                          use_pallas=False)
    want = _dense_ref(q, k_dense, v_dense, lens)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_paged_pallas_kernel_interpret_matches_xla():
    lens = [7, 3, 12]
    B, H, Hkv, D, bs = 3, 4, 2, 8, 4
    cache = PagedKVCache(24, bs, Hkv, D)
    bt = cache.build_block_table(lens)
    kc = jnp.asarray(rng.randn(24, bs, Hkv, D).astype(np.float32))
    vc = jnp.asarray(rng.randn(24, bs, Hkv, D).astype(np.float32))
    q = jnp.asarray(rng.randn(B, H, D).astype(np.float32))
    sl = jnp.asarray(lens, jnp.int32)
    btj = jnp.asarray(bt, jnp.int32)
    want = _paged_attention_xla(q, kc, vc, btj, sl, 1.0 / np.sqrt(D))
    got = _paged_attention_pallas(q, kc, vc, btj, sl, 1.0 / np.sqrt(D),
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_paged_cache_append_updates_owner_state():
    B, Hkv, D, bs = 2, 2, 8, 4
    cache = PagedKVCache(8, bs, Hkv, D)
    bt = cache.build_block_table([1, 1])
    k = rng.randn(B, Hkv, D).astype(np.float32)
    v = rng.randn(B, Hkv, D).astype(np.float32)
    cache.append(k, v, bt, np.zeros(B, np.int32))
    k_back, _ = reconstruct_kv(cache.key_cache, cache.value_cache, bt, 1)
    np.testing.assert_allclose(np.asarray(k_back)[:, 0], k, rtol=1e-6)


def test_prefill_write_vectorized_matches_stepwise():
    B, S, Hkv, D, bs = 2, 6, 2, 4, 4
    cache = PagedKVCache(8, bs, Hkv, D)
    bt = cache.build_block_table([S, S])
    k = rng.randn(B, S, Hkv, D).astype(np.float32)
    v = rng.randn(B, S, Hkv, D).astype(np.float32)
    kc, vc = write_kv_to_cache(k, v, cache.key_cache, cache.value_cache,
                               bt, np.zeros(B, np.int32))
    kc2, vc2 = cache.key_cache, cache.value_cache
    for s in range(S):
        kc2, vc2 = write_kv_to_cache(k[:, s], v[:, s], kc2, vc2, bt,
                                     np.asarray([s, s], np.int32))
    np.testing.assert_array_equal(np.asarray(kc), np.asarray(kc2))
    np.testing.assert_array_equal(np.asarray(vc), np.asarray(vc2))


def test_paged_cache_alloc_free():
    cache = PagedKVCache(8, 4, 1, 4)
    bt = cache.build_block_table([10, 5])   # 3 + 2 blocks
    assert (bt >= 0).sum() == 5
    assert len(cache._free) == 3
    cache.free_sequence(bt[1])
    assert len(cache._free) == 5
    bt2 = cache.ensure_capacity(bt[:1], [12])   # needs 4th block for row 0
    assert (bt2[0] >= 0).sum() == 4
    with pytest.raises(RuntimeError, match="out of blocks"):
        cache.build_block_table([100])


def test_block_multihead_attention_prefill_then_decode():
    B, S, H, Hkv, D, bs = 2, 6, 4, 2, 8, 4
    cache = PagedKVCache(16, bs, Hkv, D)
    bt = cache.build_block_table([S + 4] * B)
    kc, vc = cache.key_cache, cache.value_cache

    qkv_p = rng.randn(B, S, (H + 2 * Hkv) * D).astype(np.float32)
    out_p, kc, vc, sl = block_multihead_attention(
        qkv_p, kc, vc, np.zeros(B, np.int32), bt, num_heads=H, head_dim=D)
    assert out_p.shape == (B, S, H * D)
    assert list(np.asarray(sl)) == [S, S]

    # prefill numerics: causal self-attention over the 6 tokens
    qkv_r = qkv_p.reshape(B, S, H + 2 * Hkv, D)
    q, k, v = np.split(qkv_r, [H, H + Hkv], axis=2)
    qh = np.moveaxis(q, 2, 1)
    kh = np.repeat(np.moveaxis(k, 2, 1), H // Hkv, axis=1)
    vh = np.repeat(np.moveaxis(v, 2, 1), H // Hkv, axis=1)
    s = np.einsum("bhqd,bhkd->bhqk", qh, kh) / np.sqrt(D)
    causal = np.tril(np.ones((S, S), bool))
    s = np.where(causal, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True)); p /= p.sum(-1, keepdims=True)
    want_p = np.moveaxis(np.einsum("bhqk,bhkd->bhqd", p, vh),
                         1, 2).reshape(B, S, H * D)
    np.testing.assert_allclose(np.asarray(out_p), want_p, rtol=1e-4,
                               atol=1e-5)

    # decode one token: attends to the 6 cached + itself
    qkv_d = rng.randn(B, 1, (H + 2 * Hkv) * D).astype(np.float32)
    out_d, kc, vc, sl = block_multihead_attention(
        qkv_d, kc, vc, sl, bt, num_heads=H, head_dim=D)
    assert out_d.shape == (B, 1, H * D)
    assert list(np.asarray(sl)) == [S + 1, S + 1]

    k_all, v_all = reconstruct_kv(kc, vc, bt, max_len=S + 1)
    qd = qkv_d.reshape(B, 1, H + 2 * Hkv, D)[:, 0, :H]
    want_d = _dense_ref(qd, np.asarray(k_all), np.asarray(v_all),
                        [S + 1] * B).reshape(B, H * D)
    np.testing.assert_allclose(np.asarray(out_d)[:, 0], want_d,
                               rtol=1e-4, atol=1e-5)


def test_masked_multihead_attention_steps():
    B, H, D, max_len = 2, 2, 4, 8
    cache = np.zeros((2, B, H, max_len, D), np.float32)
    sl = np.zeros(B, np.int32)
    ks, vs = [], []
    outs = []
    for step in range(3):
        x = rng.randn(B, 3 * H * D).astype(np.float32)
        xr = x.reshape(B, 3, H, D)
        ks.append(xr[:, 1]); vs.append(xr[:, 2])
        out, cache, sl = masked_multihead_attention(x, cache, sl,
                                                    num_heads=H)
        outs.append((xr[:, 0], np.asarray(out)))
    assert list(np.asarray(sl)) == [3, 3]
    # final step must equal dense attention over all 3 cached tokens
    q_last = outs[-1][0]
    k_dense = np.stack(ks, axis=1)   # [B, 3, H, D]
    v_dense = np.stack(vs, axis=1)
    want = _dense_ref(q_last, k_dense, v_dense, [3, 3]).reshape(B, H * D)
    np.testing.assert_allclose(outs[-1][1], want, rtol=1e-4, atol=1e-5)


def test_predictor_roundtrip(tmp_path):
    from paddle_tpu import nn, jit
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.jit.api import InputSpec

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 3))
    path = str(tmp_path / "deploy" / "model")
    jit.save(net, path,
             input_spec=[InputSpec([None, 4], "float32", name="feats")])

    cfg = Config()
    cfg.set_model(path)
    pred = create_predictor(cfg)
    assert pred.get_input_names() == ["feats"]
    x = rng.randn(5, 4).astype(np.float32)
    h = pred.get_input_handle("feats")
    h.copy_from_cpu(x)
    assert pred.run()
    out = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
    assert out.shape == (5, 3)
    # numerics: same as direct forward
    want = np.asarray(net(paddle.to_tensor(x))._value)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    # list-style run
    outs = pred.run([x])
    np.testing.assert_allclose(outs[0], want, rtol=1e-5, atol=1e-6)


def test_llama_generate_cache_matches_full_recompute():
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    paddle.seed(0)
    cfg = llama_tiny_config(num_hidden_layers=2, hidden_size=64,
                            num_attention_heads=4, num_key_value_heads=2,
                            vocab_size=128, intermediate_size=128)
    model = LlamaForCausalLM(cfg)
    model.eval()
    ids = np.array([[5, 17, 42], [7, 99, 3]], np.int64)

    out = model.generate(paddle.to_tensor(ids), max_new_tokens=5)
    out_np = np.asarray(out._value)
    assert out_np.shape == (2, 8)
    np.testing.assert_array_equal(out_np[:, :3], ids)

    # full-recompute greedy reference (no cache): must match exactly
    cur = ids.copy()
    from paddle_tpu.autograd import no_grad
    with no_grad():
        for _ in range(5):
            logits = model(paddle.to_tensor(cur))
            nxt = np.asarray(logits._value)[:, -1, :].argmax(-1)
            cur = np.concatenate([cur, nxt[:, None].astype(np.int64)], 1)
    np.testing.assert_array_equal(out_np, cur)


def test_rope_position_ids_with_and_without_tables():
    from paddle_tpu.incubate.nn.functional import (
        fused_rotary_position_embedding)
    B, S, H, D = 1, 2, 1, 8
    q = rng.randn(B, S, H, D).astype(np.float32)
    # positions [5, 6] via position_ids must equal slicing a longer run
    q_long = np.zeros((B, 7, H, D), np.float32)
    q_long[:, 5:7] = q
    full, _, _ = fused_rotary_position_embedding(paddle.to_tensor(q_long))
    got, _, _ = fused_rotary_position_embedding(
        paddle.to_tensor(q), position_ids=np.array([5, 6], np.int32))
    np.testing.assert_allclose(np.asarray(got._value),
                               np.asarray(full._value)[:, 5:7],
                               rtol=1e-5, atol=1e-6)
    # precomputed [max_seq, dim] sin/cos tables + position_ids selects rows
    pos_all = np.arange(16)[:, None]
    inv = 1.0 / (10000.0 ** (np.arange(0, D, 2) / D))
    emb = np.concatenate([pos_all * inv, pos_all * inv], -1)
    got2, _, _ = fused_rotary_position_embedding(
        paddle.to_tensor(q), sin=np.sin(emb).astype(np.float32),
        cos=np.cos(emb).astype(np.float32),
        position_ids=np.array([5, 6], np.int32))
    np.testing.assert_allclose(np.asarray(got2._value),
                               np.asarray(got._value), rtol=1e-5,
                               atol=1e-6)


def test_llama_generate_top_p_runs():
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    paddle.seed(0)
    cfg = llama_tiny_config(num_hidden_layers=1, hidden_size=32,
                            num_attention_heads=2, num_key_value_heads=2,
                            vocab_size=64, intermediate_size=64)
    model = LlamaForCausalLM(cfg)
    model.eval()
    ids = np.array([[1, 2]], np.int64)
    out = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                         top_p=0.9, temperature=0.8, seed=7)
    arr = np.asarray(out._value)
    assert arr.shape == (1, 6)
    assert ((arr >= 0) & (arr < 64)).all()


# ---------------------------------------------------------------------------
# continuous batching (VERDICT round-2 item 7)
# ---------------------------------------------------------------------------
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


def test_continuous_batching_matches_sequential():
    """Three requests of different lengths admitted at different times
    must produce exactly the tokens each would get alone (greedy)."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    prompts = [np.array([3, 14, 15, 92, 65], np.int64),
               np.array([1, 2], np.int64),
               np.array([42, 7, 9], np.int64)]
    budgets = [6, 9, 4]

    # sequential reference: the model's own KV-cache generate loop
    want = []
    for p, n in zip(prompts, budgets):
        out = model.generate(paddle.to_tensor(p[None, :]),
                             max_new_tokens=n)
        want.append(np.asarray(out._value)[0, len(p):].tolist())

    eng = ContinuousBatchingEngine(model, max_batch_size=4,
                                   num_blocks=64, block_size=4)
    # staggered admission: r0 first, r1 after one step (r0 mid-decode),
    # r2 after another step
    r0 = eng.add_request(prompts[0], budgets[0])
    eng.step()
    r1 = eng.add_request(prompts[1], budgets[1])
    eng.step()
    r2 = eng.add_request(prompts[2], budgets[2])
    eng.run_to_completion()

    assert eng.result(r0) == want[0]
    assert eng.result(r1) == want[1]
    assert eng.result(r2) == want[2]


def test_continuous_batching_slot_reuse_and_eviction():
    """Finished requests free their pages; later requests reuse them
    (pool smaller than the total footprint of all requests)."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                   num_blocks=8, block_size=4)
    # each request needs ceil((3+6)/4)=3 blocks; pool of 8 can hold at
    # most 2 at once; 4 requests must cycle through slots
    rids = [eng.add_request(np.array([i + 1, i + 2, i + 3], np.int64),
                            max_new_tokens=6) for i in range(4)]
    outs = eng.run_to_completion()
    assert set(outs) == set(rids)
    for rid in rids:
        assert len(eng.result(rid)) == 6
    # all pages returned to the pool
    assert len(eng.caches[0]._free) == 8


def test_compiled_decode_compiles_once_across_churn():
    """The step is ONE jitted module a token budget: admission,
    eviction, and re-admission (occupancy 0 -> 2 -> 1 -> 2 -> ... -> 0
    with a 2-slot engine cycling 4 requests) must trace each budget at
    most once, with tokens byte-identical to each request's solo eager
    generate (pattern: the compile-hygiene gate in
    tests/test_sparse_nn.py)."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    prompts = [np.array([3, 14, 15, 92, 65], np.int64),
               np.array([1, 2], np.int64),
               np.array([42, 7, 9], np.int64),
               np.array([8, 8, 120, 4], np.int64)]
    budgets = [6, 9, 4, 7]
    want = []
    for p, n in zip(prompts, budgets):
        out = model.generate(paddle.to_tensor(p[None, :]),
                             max_new_tokens=n)
        want.append(np.asarray(out._value)[0, len(p):].tolist())

    eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                   num_blocks=16, block_size=4)
    rids = [eng.add_request(p, n) for p, n in zip(prompts, budgets)]
    eng.run_to_completion()
    for rid, w in zip(rids, want):
        assert eng.result(rid) == w
    counts = dict(eng.mixed.compile_counts)
    assert set(counts) <= set(eng.token_budgets)
    assert all(v == 1 for v in counts.values()), (
        "the step recompiled under slot churn: occupancy changes "
        "must be masked, never re-shaped")
    # a second wave through the SAME engine reuses the compiled steps
    rid2 = eng.add_request(prompts[0], budgets[0])
    eng.run_to_completion()
    assert eng.result(rid2) == want[0]
    assert eng.mixed.compile_counts == counts


def test_engine_rejects_request_beyond_table_width():
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                   num_blocks=8, block_size=4,
                                   max_seq_len=8)
    with pytest.raises(ValueError, match="raise max_seq_len"):
        eng.add_request(np.arange(1, 7, dtype=np.int64),
                        max_new_tokens=8)   # needs 14 > 8 tokens


def test_masked_slots_do_not_perturb_live_request():
    """A request decoding alongside empty (masked) slots must produce
    the same tokens as one occupying a full engine: inactive-slot
    writes land in the sink page, never in live pages."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    p = np.array([7, 11, 13], np.int64)
    ref = model.generate(paddle.to_tensor(p[None, :]), max_new_tokens=6)
    ref_toks = np.asarray(ref._value)[0, 3:].tolist()
    eng = ContinuousBatchingEngine(model, max_batch_size=4,
                                   num_blocks=32, block_size=4)
    rid = eng.add_request(p, max_new_tokens=6)
    eng.run_to_completion()
    assert eng.result(rid) == ref_toks
    # sink page is not in the free list and was never handed out
    assert eng.caches[0].sink not in eng.caches[0]._free
    assert len(eng.caches[0]._free) == 32


def test_continuous_batching_eos_stops_early():
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    p = np.array([5, 6, 7], np.int64)
    ref = model.generate(paddle.to_tensor(p[None, :]), max_new_tokens=8)
    ref_toks = np.asarray(ref._value)[0, 3:].tolist()
    eos = ref_toks[2]          # force an early stop at the 3rd token
    eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                   num_blocks=32, block_size=4)
    rid = eng.add_request(p, max_new_tokens=8, eos_token_id=eos)
    eng.run_to_completion()
    assert eng.result(rid) == ref_toks[:3]


def test_lazy_alloc_truncates_victim_instead_of_wedging_batch():
    """Robustness: with lazy page allocation the pool CAN run dry
    mid-decode.  The victim request must be finished early with
    ``truncated=True`` — its pages recycled, the rest of the batch
    decoding on — instead of an exception escaping step()."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    # 4 pages x 4 tokens = 16 cache positions; two prompt-3 requests
    # each budgeting 12 new tokens CANNOT both finish
    eng = ContinuousBatchingEngine(model, max_batch_size=2, num_blocks=4,
                                   block_size=4, max_seq_len=32,
                                   lazy_alloc=True)
    r0 = eng.add_request(np.array([1, 2, 3], np.int64), max_new_tokens=12)
    r1 = eng.add_request(np.array([4, 5, 6], np.int64), max_new_tokens=12)
    eng.run_to_completion()                # must terminate, not raise
    reqs = [eng.finished[r] for r in (r0, r1)]
    assert any(r.truncated for r in reqs)
    for r in reqs:
        # a truncated request still returns every token it decoded
        assert 0 < len(r.output_ids) <= 12
        assert r.truncated or len(r.output_ids) == 12
    # every page back in the pool; engine reusable afterwards
    assert len(eng.caches[0]._free) == 4
    r2 = eng.add_request(np.array([9], np.int64), max_new_tokens=3)
    eng.run_to_completion()
    assert len(eng.result(r2)) == 3
    assert not eng.finished[r2].truncated


# ---------------------------------------------------------------------------
# chunked prefill with prefix caching
# ---------------------------------------------------------------------------
def _ref_tokens(model, prompt, budget):
    out = model.generate(paddle.to_tensor(prompt[None, :]),
                         max_new_tokens=budget)
    return np.asarray(out._value)[0, len(prompt):].tolist()


def test_chunked_prefill_parity_and_compile_bound():
    """Prompts shorter than the chunk plus one longer than it (10 ->
    chunks 8+2, interleaved with decode) must all match eager generate,
    with total compiles bounded by the BUDGET count — not the 3
    distinct prompt lengths."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    prompts = [np.array([7, 9, 2], np.int64),
               np.array([3, 14, 15, 92, 65], np.int64),
               np.arange(1, 11, dtype=np.int64)]         # 10 -> chunked
    budgets = [4, 4, 4]
    want = [_ref_tokens(model, p, n) for p, n in zip(prompts, budgets)]
    eng = ContinuousBatchingEngine(model, max_batch_size=4,
                                   num_blocks=64, block_size=4,
                                   prefill_chunk_size=8)
    assert eng.chunk_size == 8 and eng.token_budgets == (4, 8, 16)
    rids = [eng.add_request(p, n) for p, n in zip(prompts, budgets)]
    eng.run_to_completion()
    for rid, w in zip(rids, want):
        assert eng.result(rid) == w
    # chunk offsets and prompt lengths are traced data: the len-10
    # prompt's 8+2 chunks added no trace beyond the budgets
    assert set(eng.mixed.compile_counts) <= set(eng.token_budgets)
    assert all(v == 1 for v in eng.mixed.compile_counts.values())


def test_prefix_cache_cow_refcounts_and_leak_free():
    """Shared prefix: request B reuses A's cached prompt pages and only
    prefills its suffix; request C (identical prompt) takes the
    whole-prompt-hit copy-on-write path.  All outputs byte-identical
    to eager generate; after run_to_completion no page leaks — every
    page is either free or held exactly once by the prefix table."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    P = np.array([5, 17, 42, 7, 99, 3, 11, 23], np.int64)   # 2 full blocks
    B = np.concatenate([P, [77, 8]])
    refA = _ref_tokens(model, P, 4)
    refB = _ref_tokens(model, B, 4)
    eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                   num_blocks=32, block_size=4,
                                   prefill_chunk_size=4,
                                   enable_prefix_cache=True)
    ra = eng.add_request(P, 4)
    eng.run_to_completion()
    rb = eng.add_request(B, 4)          # hits both prompt pages of A
    rc = eng.add_request(P, 4)          # whole-prompt hit -> COW
    eng.run_to_completion()
    assert eng.result(ra) == refA
    assert eng.result(rb) == refB
    assert eng.result(rc) == refA
    pc = eng.prefix_cache
    assert pc.misses == 1 and pc.hits == 2
    # B reused 8 prefix tokens; C's whole-prompt hit is capped one
    # short so the last position re-runs to sample the first token
    assert pc.hit_tokens == 8 + 7
    assert eng.finished[rb].prefix_hit_tokens == 8
    assert eng.finished[rc].prefix_hit_tokens == 7
    # refcount leak check: every page free or table-held exactly once
    c0 = eng.caches[0]
    cached = pc.cached_blocks()
    assert all(c0.refcount(b) == 1 for b in cached)
    assert len(c0._free) + len(cached) == c0.num_blocks
    assert len(c0._free) < c0.num_blocks     # prefixes actually cached


@pytest.mark.slow
def test_prefix_eviction_honors_refcounts():
    """Pool pressure evicts only table entries NO live request holds:
    a prefix still referenced by a running request's block table
    survives, and that request's tokens stay byte-identical."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    P = np.array([5, 17, 42, 7, 99, 3, 11, 23], np.int64)
    Q = np.array([9, 9, 8, 1, 66, 4, 12, 30], np.int64)
    B = np.concatenate([P, [77, 8]])                       # shares P
    R = np.arange(2, 34, 2, dtype=np.int64)                # 16 tokens
    refB = _ref_tokens(model, B, 6)
    refR = _ref_tokens(model, R, 8)
    eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                   num_blocks=10, block_size=4,
                                   max_seq_len=24,
                                   prefill_chunk_size=8,
                                   enable_prefix_cache=True)
    eng.add_request(P, 2)
    eng.run_to_completion()              # P's 2 pages cached, ref==1
    eng.add_request(Q, 2)
    eng.run_to_completion()              # Q's 2 pages cached, ref==1
    pc = eng.prefix_cache
    assert len(pc) == 4
    rb = eng.add_request(B, 6)           # shares P pages -> ref 2
    eng.step()
    assert eng.finished.get(rb) is None  # B still running
    rr = eng.add_request(R, 8)           # needs 6 pages; free == 4 ->
    eng.run_to_completion()              # must evict Q's (ref==1) pages
    assert pc.evictions == 2
    assert eng.result(rb) == refB        # shared P pages never reclaimed
    assert eng.result(rr) == refR
    # P's entries survived (they were shared while pressure hit)
    assert pc.match(P) != []
    c0 = eng.caches[0]
    cached = pc.cached_blocks()
    assert all(c0.refcount(b) == 1 for b in cached)
    assert len(c0._free) + len(cached) == c0.num_blocks


@pytest.mark.slow
def test_prompt_length_sweep_few_compiles():
    """Mixed-length sweep: 9 distinct prompt lengths, every output
    parity-exact, compiles bounded by the budget set — not one trace
    per distinct length."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    rng_ = np.random.RandomState(3)
    lengths = [2, 3, 4, 5, 7, 9, 11, 13, 16]
    prompts = [rng_.randint(1, 128, (n,)).astype(np.int64)
               for n in lengths]
    want = [_ref_tokens(model, p, 3) for p in prompts]
    eng = ContinuousBatchingEngine(model, max_batch_size=4,
                                   num_blocks=96, block_size=4,
                                   prefill_chunk_size=16)
    rids = [eng.add_request(p, 3) for p in prompts]
    eng.run_to_completion()
    for rid, w in zip(rids, want):
        assert eng.result(rid) == w
    assert eng.mixed.total_compiles <= len(eng.token_budgets)


@pytest.mark.slow
def test_concurrent_divergent_suffixes_share_prefix():
    """Two requests sharing a prefix admitted TOGETHER (second hits the
    pages the first published), divergent suffixes decoded
    concurrently — plus a chunked long prompt whose prefix is itself a
    cache hit.  All byte-identical to solo eager generate."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    P = np.array([5, 17, 42, 7, 99, 3, 11, 23], np.int64)
    b1 = np.concatenate([P, [77, 8]])                     # 10 -> chunked
    b2 = np.concatenate([P, [14, 50, 2]])
    long = np.concatenate(
        [P, [61, 5, 44, 9, 28, 33, 2, 71, 19, 90]])      # hit + 10-suffix
    refs = [_ref_tokens(model, p, 5) for p in (b1, b2, long)]
    eng = ContinuousBatchingEngine(model, max_batch_size=3,
                                   num_blocks=64, block_size=4,
                                   prefill_chunk_size=8,
                                   enable_prefix_cache=True)
    r1 = eng.add_request(b1, 5)         # miss; publishes P's pages
    eng.run_to_completion()
    r2 = eng.add_request(b2, 5)         # hit, short suffix
    r3 = eng.add_request(long, 5)       # hit + CHUNKED suffix (8+2)
    eng.run_to_completion()             # divergent suffixes concurrent
    for rid, w in zip((r1, r2, r3), refs):
        assert eng.result(rid) == w
    pc = eng.prefix_cache
    assert pc.hits == 2                 # b2 and the long prompt hit
    c0 = eng.caches[0]
    cached = pc.cached_blocks()
    assert all(c0.refcount(b) == 1 for b in cached)
    assert len(c0._free) + len(cached) == c0.num_blocks


@pytest.mark.slow
def test_lazy_alloc_matches_eager_when_pool_suffices():
    """Lazy growth is a capacity policy, not a math change: with enough
    pages the tokens are byte-identical to the eager-allocation engine."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    prompts = [np.array([3, 1, 4], np.int64), np.array([1, 5], np.int64)]
    outs = {}
    for lazy in (False, True):
        eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                       num_blocks=32, block_size=4,
                                       lazy_alloc=lazy)
        rids = [eng.add_request(p, max_new_tokens=4) for p in prompts]
        eng.run_to_completion()
        outs[lazy] = [eng.result(r) for r in rids]
        assert not any(eng.finished[r].truncated for r in rids)
    assert outs[False] == outs[True]


# ---------------------------------------------------------------------------
# fused mixed prefill+decode step (ISSUE round-11 tentpole,
# arXiv:2604.15464 Ragged Paged Attention)
# ---------------------------------------------------------------------------
def test_mixed_step_parity_compile_bound_under_churn():
    """ONE fused MixedStep module per token budget must handle an
    admission-churned mix — staggered admission, decode-only stretches,
    a chunked long prompt riding along with running decodes — with
    tokens byte-identical to each request's solo eager generate and
    total compiles <= the budget-set size."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    # same prompts/budgets as the chunked-prefill parity test: the
    # eager references share shapes (suite-budget control)
    prompts = [np.array([7, 9, 2], np.int64),
               np.array([3, 14, 15, 92, 65], np.int64),
               np.arange(1, 11, dtype=np.int64)]     # 10 -> chunks of 4
    budgets = [4, 4, 4]
    want = [_ref_tokens(model, p, n) for p, n in zip(prompts, budgets)]
    eng = ContinuousBatchingEngine(model, max_batch_size=4,
                                   num_blocks=64, block_size=4,
                                   prefill_chunk_size=4)
    assert eng.token_budgets == (4, 8)
    r0 = eng.add_request(prompts[0], budgets[0])
    eng.step()                          # r0 decoding alone
    r1 = eng.add_request(prompts[1], budgets[1])
    r2 = eng.add_request(prompts[2], budgets[2])
    eng.run_to_completion()             # chunks packed WITH r0's decode
    for rid, w in zip((r0, r1, r2), want):
        assert eng.result(rid) == w
    assert eng.mixed.total_compiles <= len(eng.token_budgets), (
        "mixed step compiled %d times for %d budgets"
        % (eng.mixed.total_compiles, len(eng.token_budgets)))
    # a second wave through the SAME engine adds no trace
    pre = eng.mixed.total_compiles
    r3 = eng.add_request(prompts[0], budgets[0])
    eng.run_to_completion()
    assert eng.result(r3) == want[0]
    assert eng.mixed.total_compiles == pre
    # no page leaks across the whole run
    assert len(eng.caches[0]._free) == 64


@pytest.mark.parametrize("kw, exc, word", [
    (dict(mixed_step=False), ValueError, "removed in PR 29"),
    (dict(prefill_buckets="auto"), TypeError, "prefill_buckets"),
])
def test_split_path_flags_are_gone(kw, exc, word):
    """The fused mixed step is the one serving path: the flag that
    chose the split path has one legal value, the bucket argument is no
    argument."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    with pytest.raises(exc, match=word):
        ContinuousBatchingEngine(_tiny_model(), **kw)


def _family_model(family):
    if family == "llama":
        return _tiny_model()
    if family == "mixtral":
        from paddle_tpu.models.mixtral import (MixtralForCausalLM,
                                               mixtral_tiny_config)
        paddle.seed(0)
        return MixtralForCausalLM(
            mixtral_tiny_config(num_hidden_layers=2)).eval()
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2ForCausalLM,
                                               deepseek_v2_tiny_config)
    paddle.seed(11)
    return DeepseekV2ForCausalLM(deepseek_v2_tiny_config(
        n_routed_experts=4, router_experts=8, first_held_expert=2)).eval()


@pytest.mark.parametrize("family", ["llama", "mixtral", "deepseek_v2"])
def test_engine_without_flags_is_the_mixed_engine(family):
    """An engine built with no path flag and one built with the
    benchmark's literal ``mixed_step=True`` are the same engine: same
    budgets and chunk, the same lowered program at every budget, the
    same tokens (which are eager ``generate``'s)."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _family_model(family)
    prompts = [np.array([7, 9, 2], np.int64),
               np.arange(1, 11, dtype=np.int64)]      # 10 -> chunked
    kw = dict(max_batch_size=2, num_blocks=32, block_size=4,
              max_seq_len=32)
    plain = ContinuousBatchingEngine(model, **kw)
    flagged = ContinuousBatchingEngine(model, mixed_step=True, **kw)
    assert plain.token_budgets == flagged.token_budgets == (2, 4, 8, 16,
                                                            32, 64)
    assert plain.chunk_size == flagged.chunk_size == 32
    T = plain.token_budgets[2]
    assert plain.mixed.aot_lower(T).as_text() \
        == flagged.mixed.aot_lower(T).as_text()
    outs = []
    for eng in (plain, flagged):
        rids = [eng.add_request(p, 3) for p in prompts]
        eng.run_to_completion()
        outs.append([eng.result(r) for r in rids])
    assert outs[0] == outs[1] == [_ref_tokens(model, p, 3)
                                  for p in prompts]


# each case: [(q_len, kv_len)] spans (kv_len INCLUDES the span); the
# page size where it is not 4
RAGGED_SWEEP = {
    "decode_only": [(1, 5), (1, 9), (1, 1), (1, 16)],
    "fresh_chunk_and_decode": [(6, 6), (1, 7)],
    "mid_prompt_chunk": [(4, 12), (8, 8), (1, 3)],
    "ragged_mix": [(3, 11), (1, 13), (5, 5), (2, 10)],
    # a prefix hit: the span's first row already sits at position 8
    "page_aligned_suffix": [(8, 16)],
    "padded_span_tail": [(1, 6), (7, 15), (0, 1), (0, 1)],
    # spans that end mid-page beside a q_len == 0 padding span
    "ends_mid_page": [(1, 40), (20, 37), (9, 9), (0, 1)],
    # a chunk of several q tiles (32 tokens at its GQA 4:1) that
    # starts mid-page behind a prefix, between two decode spans
    "chunk_over_one_tile": [(1, 300), (140, 290), (1, 7)],
    # "wide_": 16-token pages, tables at least two 512-key blocks long
    # (blocks wholly under a tile's diagonal take the unmasked body,
    # the rest the 128-key tail walk): a chunk whose tiles start past
    # one whole block; decode spans at one block of keys, one past it
    # and one past two; a chunk tile whose diagonal straddles a block
    # edge; and all of them in one pack
    "wide_chunk_past_block": [(64, 664), (1, 9)],
    "wide_decode_on_block_edge": [(1, 512), (1, 513), (1, 1025)],
    "wide_diagonal_straddles_block": [(40, 530), (0, 1)],
    "wide_mix": [(1, 512), (64, 664), (1, 513), (40, 530), (1, 1100),
                 (0, 1)],
}
_TIER1_RAGGED = {("ragged_mix", "float32"),
                 ("chunk_over_one_tile", "bfloat16"),
                 ("wide_mix", "float32")}
# pages of the "wide_" cases' tables: two whole 512-key blocks and more
WIDE_TABLE = 2 * 512 // 16 + 2


def _ragged_case(spans, bs, Hkv, H, D, dtype, seed=42, min_width=2):
    """The packed operands of one sweep case, pools in ``dtype``."""
    rng_ = np.random.RandomState(seed)
    W = max(min_width, max(-(-kv // bs) for _, kv in spans))
    nb = W * len(spans)
    kc = jnp.asarray(rng_.randn(nb, bs, Hkv, D), dtype)
    vc = jnp.asarray(rng_.randn(nb, bs, Hkv, D), dtype)
    cache = PagedKVCache(nb, bs, Hkv, D)
    bt = np.stack([
        cache.build_block_table([kv_len], max_blocks=W)[0] if q_len
        else np.full((W,), -1, np.int32) for q_len, kv_len in spans])
    T = sum(q for q, _ in spans)
    q = jnp.asarray(rng_.randn(T, H, D), dtype)
    q_offsets, off = [], 0
    for q_len, _ in spans:
        q_offsets.append(off if q_len else T)
        off += q_len
    return (q, kc, vc, bt, np.asarray(q_offsets, np.int32),
            np.asarray([q for q, _ in spans], np.int32),
            np.asarray([kv for _, kv in spans], np.int32))


@pytest.mark.parametrize("case,dtype", [
    pytest.param(c, d, marks=() if (c, d) in _TIER1_RAGGED
                 else pytest.mark.slow)
    for c in sorted(RAGGED_SWEEP) for d in ("float32", "bfloat16")])
def test_ragged_kernel_interpret_matches_reference_sweep(case, dtype):
    """Pallas ragged-paged-attention kernel (interpret mode) vs the XLA
    gather reference across span mixes: decode-only packs, chunks
    starting mid-page and page-aligned, prefix-hit-style suffix spans,
    spans that end mid-page, a chunk longer than one q tile, GQA
    grouping, and budget padding (zero-length spans), in float32 and in
    bfloat16 (where the probabilities are rounded to the pool's type
    for ``p.V``, as on the chip).  Two cases run in tier 1, the rest in
    the slow lane."""
    from paddle_tpu.ops.paged_attention import (_ragged_attention_xla,
                                                ragged_paged_attention)
    spans = RAGGED_SWEEP[case]
    wide = case.startswith("wide_")
    bs, H = (16, 8) if wide or case == "chunk_over_one_tile" else (4, 4)
    q, kc, vc, bt, q_offsets, q_lens, kv_lens = _ragged_case(
        spans, bs, 2, H, 16, dtype, min_width=WIDE_TABLE if wide else 2)
    want = _ragged_attention_xla(
        q.astype(jnp.float32), kc.astype(jnp.float32),
        vc.astype(jnp.float32), jnp.asarray(bt), jnp.asarray(q_offsets),
        jnp.asarray(q_lens), jnp.asarray(kv_lens), 1.0 / np.sqrt(16))
    got = ragged_paged_attention(
        q, kc, vc, bt, q_offsets, q_lens, kv_lens, interpret=True)
    assert got.dtype == q.dtype
    tol = dict(rtol=2e-4, atol=2e-5) if dtype == "float32" \
        else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), err_msg=str(spans),
                               **tol)


# ---------------------------------------------------------------------------
# round 17: double-buffered page DMA + fused RoPE+QKV epilogue
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("geometry", ["small_pages", "wide_blocks"])
def test_ragged_pipelined_prefetch_clamp_poisoned_pages(geometry):
    """r11 poison invariant, extended to the double-buffered kernel:
    prefetching page i+1 while attending page i must NEVER touch a
    page past the span's used block count — including the last-page
    boundary (a span whose used count fills the whole table, where an
    unclamped prefetch would read bt[s, W]).  Every unused page (and
    the poison page the padded table entries point at) is NaN'd; the
    kernel's output must be BYTE-IDENTICAL to its clean-pool run —
    for the ragged launch (whose key blocks hold several pages, so a
    partly filled block must not fetch its tail; ``wide_blocks``: 16-
    token pages, 512-key blocks, the 128-key tail walk) and for both
    decode kernels — and match the XLA reference on the clean pool."""
    from paddle_tpu.ops.paged_attention import _ragged_attention_xla
    if geometry == "small_pages":
        bs, Hkv, H, D, nb, W = 4, 2, 4, 16, 32, 4
        # last span uses ALL W=4 pages: the prefetch-clamp boundary case
        spans = [(1, 5), (4, 12), (8, 8), (1, 16), (2, 16)]
    else:
        bs, Hkv, H, D, W = 16, 2, 8, 16, WIDE_TABLE
        spans = [(1, 512), (64, 664), (1, 513), (40, 530),
                 (3, W * bs)]
        nb = sum(-(-kv // bs) for _, kv in spans) + 24
    rng_ = np.random.RandomState(3)
    kc = jnp.asarray(rng_.randn(nb, bs, Hkv, D).astype(np.float32))
    vc = jnp.asarray(rng_.randn(nb, bs, Hkv, D).astype(np.float32))
    cache = PagedKVCache(nb, bs, Hkv, D)
    poison = cache.allocate_block()
    rows, used_pages = [], {poison}
    for q_len, kv_len in spans:
        used = -(-kv_len // bs)
        tab = cache.build_block_table([kv_len], max_blocks=W)[0]
        used_pages.update(int(b) for b in tab[:used])
        tab[used:] = poison          # padded entries -> the poison page
        rows.append(tab)
    bt = np.stack(rows)
    T = sum(q for q, _ in spans)
    q = rng_.randn(T, H, D).astype(np.float32)
    q_offsets = np.cumsum([0] + [q for q, _ in spans[:-1]]).astype(np.int32)
    q_lens = np.asarray([q for q, _ in spans], np.int32)
    kv_lens = np.asarray([kv for _, kv in spans], np.int32)
    unused = np.asarray(sorted(set(range(nb)) - used_pages)
                        + [poison], np.int32)
    kc_p = kc.at[unused].set(np.float32(np.nan))
    vc_p = vc.at[unused].set(np.float32(np.nan))
    args = (bt, q_offsets, q_lens, kv_lens)
    want = _ragged_attention_xla(
        jnp.asarray(q), kc, vc, jnp.asarray(bt), jnp.asarray(q_offsets),
        jnp.asarray(q_lens), jnp.asarray(kv_lens), 1.0 / np.sqrt(D))
    clean = np.asarray(ragged_paged_attention(
        q, kc, vc, *args, interpret=True))
    poisoned = np.asarray(ragged_paged_attention(
        q, kc_p, vc_p, *args, interpret=True))
    assert np.isfinite(poisoned).all()
    np.testing.assert_array_equal(clean, poisoned)
    np.testing.assert_allclose(clean, np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    if geometry != "small_pages":
        return
    # decode kernel: same invariant (full-table sequence included)
    sl = np.asarray([5, 16], np.int32)
    bt2 = np.stack([rows[0], rows[3]])
    for pipelined in (True, False):
        clean = np.asarray(paged_attention(
            q[:2], kc, vc, bt2, sl, interpret=True,
            pipelined=pipelined))
        poisoned = np.asarray(paged_attention(
            q[:2], kc_p, vc_p, bt2, sl, interpret=True,
            pipelined=pipelined))
        assert np.isfinite(poisoned).all()
        np.testing.assert_array_equal(clean, poisoned)


@pytest.mark.slow
def test_ragged_pipelined_matches_sync_fp32_byte_identical():
    """The ragged launch against the XLA reference at float tolerance
    (its sync-DMA twin went with the span window: the tiles sum in
    another order than the reference, so not byte for byte).  The
    decode kernel keeps its twin: double buffering only reorders DMA
    issue/wait — the fp32 compute stream is the SAME ops on the same
    values, so the pipelined kernel must be byte-identical to the r16
    sync-DMA kernel (interpret mode)."""
    from paddle_tpu.ops.paged_attention import _ragged_attention_xla
    bs, Hkv, H, D, nb = 4, 2, 4, 16, 64
    rng_ = np.random.RandomState(11)
    kc = jnp.asarray(rng_.randn(nb, bs, Hkv, D).astype(np.float32))
    vc = jnp.asarray(rng_.randn(nb, bs, Hkv, D).astype(np.float32))
    cache = PagedKVCache(nb, bs, Hkv, D)
    spans = [(3, 11), (1, 13), (5, 5), (2, 10), (1, 1)]
    W = 4
    bt = np.stack([cache.build_block_table([kv], max_blocks=W)[0]
                   for _, kv in spans])
    T = sum(q for q, _ in spans)
    q = rng_.randn(T, H, D).astype(np.float32)
    q_offsets = np.cumsum([0] + [q for q, _ in spans[:-1]]).astype(np.int32)
    q_lens = np.asarray([q for q, _ in spans], np.int32)
    kv_lens = np.asarray([kv for _, kv in spans], np.int32)
    got = np.asarray(ragged_paged_attention(
        q, kc, vc, bt, q_offsets, q_lens, kv_lens, interpret=True))
    want = np.asarray(_ragged_attention_xla(
        jnp.asarray(q), kc, vc, jnp.asarray(bt), jnp.asarray(q_offsets),
        jnp.asarray(q_lens), jnp.asarray(kv_lens), 1.0 / np.sqrt(D)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    sl = np.asarray([7, 12], np.int32)
    d_outs = [np.asarray(paged_attention(
        q[:2], kc, vc, bt[:2], sl, interpret=True, pipelined=p))
        for p in (True, False)]
    np.testing.assert_array_equal(d_outs[0], d_outs[1])


def test_rope_qkv_epilogue_xla_matches_incubate_bytewise():
    """The serving steps' fused epilogue (XLA path — what every CPU
    dryrun engine compiles) must be BYTE-identical to the
    fused_rotary_position_embedding path it replaced, and its absmax
    rows bit-identical to what the quantized write paths recompute —
    that identity is what keeps fp32 engines byte-identical end-to-end
    across the round-17 rewiring."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.incubate.nn.functional import \
        fused_rotary_position_embedding
    from paddle_tpu.ops.pallas_kernels import (rope_qkv_epilogue,
                                               rope_tables_for_positions)
    rng_ = np.random.RandomState(2)
    T, H, Hkv, D = 9, 4, 2, 16
    q = rng_.randn(1, T, H, D).astype(np.float32)
    k = rng_.randn(1, T, Hkv, D).astype(np.float32)
    v = rng_.randn(1, T, Hkv, D).astype(np.float32)
    pos = rng_.randint(0, 900, (T,)).astype(np.int32)
    qt, kt, _ = fused_rotary_position_embedding(
        Tensor._from_value(jnp.asarray(q)),
        Tensor._from_value(jnp.asarray(k)),
        position_ids=Tensor._from_value(jnp.asarray(pos[None, :])),
        rotary_emb_base=10000.0)
    cos, sin = rope_tables_for_positions(jnp.asarray(pos), D, 10000.0)
    q2, k2, ka, va = rope_qkv_epilogue(
        jnp.asarray(q[0]), jnp.asarray(k[0]), jnp.asarray(v[0]),
        cos, sin, with_amax=True, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(qt._value)[0],
                                  np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(kt._value)[0],
                                  np.asarray(k2))
    np.testing.assert_array_equal(
        np.asarray(ka),
        np.max(np.abs(np.asarray(k2, np.float32)), -1))
    np.testing.assert_array_equal(
        np.asarray(va),
        np.max(np.abs(np.asarray(v[0], np.float32)), -1))


@pytest.mark.slow
def test_rope_qkv_epilogue_interpret_matches_xla():
    """The Pallas epilogue kernel (interpret mode, incl. the row-tile
    padding path) agrees with the XLA reference at ULP level for the
    rotation and BITWISE for the absmax rows."""
    from paddle_tpu.ops.pallas_kernels import (rope_qkv_epilogue,
                                               rope_tables_for_positions)
    rng_ = np.random.RandomState(4)
    for T in (8, 13):                     # aligned + padded row tiles
        H, Hkv, D = 4, 2, 16
        q = jnp.asarray(rng_.randn(T, H, D).astype(np.float32))
        k = jnp.asarray(rng_.randn(T, Hkv, D).astype(np.float32))
        v = jnp.asarray(rng_.randn(T, Hkv, D).astype(np.float32))
        pos = jnp.asarray(rng_.randint(0, 100, (T,)).astype(np.int32))
        cos, sin = rope_tables_for_positions(pos, D, 10000.0)
        ref = rope_qkv_epilogue(q, k, v, cos, sin, with_amax=True,
                                use_pallas=False)
        got = rope_qkv_epilogue(q, k, v, cos, sin, with_amax=True,
                                interpret=True)
        for r, g in zip(ref[:3], got[:3]):
            np.testing.assert_allclose(np.asarray(r), np.asarray(g),
                                       rtol=4e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(ref[3]),
                                      np.asarray(got[3]))
