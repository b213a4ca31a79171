"""Context-parallel multi-chip serving (round-22 tentpole).

Runs on the conftest-forced 8-device CPU mesh (the shared dryrun setup,
paddle_tpu/testing/dryrun.py).  A ``cp`` mesh axis stripes every KV
pool's SLOT dim — chip r holds slots ``[r*bs/cp, (r+1)*bs/cp)`` of
every page — so per-chip pool HBM is 1/cp while the page table,
refcounts, COW and prefix keys stay chip-local.  Each chip computes
ragged attention over its local stripe (the partial-softmax kernel
variants) and the per-token ``(o, m, l)`` triples merge across the cp
axis with the ONE shared online-softmax helper
(ops/online_softmax.py).  The contract gated here:

- tokens BYTE-IDENTICAL to the single-chip engine on the same workload
  (cp=2 in tier-1; cp=4, cp x tp, prefix-COW and the chunked sweep in
  the slow lane);
- per-chip KV-pool bytes exactly 1/cp (slot-striped pages);
- compile count still bounded by the token-budget-set size;
- the shared online-softmax update is byte-identical to the expression
  sequence the kernels carried inline before round 22, and the stripe
  merge reproduces the full softmax;
- actionable construction-time errors for non-dividing block_size
  and int8 pools under cp.

Budget note: the tier-1 suite runs AT the 870s timeout — only the cp=2
parity test, the (sub-second) helper-parity test and the validation
test are unmarked; every sweep is @slow.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.testing.dryrun import force_cpu_devices

force_cpu_devices(8)     # no-op under conftest; the documented entry

from paddle_tpu.inference.serving import (  # noqa: E402
    ContinuousBatchingEngine)
from paddle_tpu.jit.spmd import cp_mesh  # noqa: E402

PROMPTS = [np.array([7, 9, 2], np.int64),
           np.array([3, 14, 15, 92, 65], np.int64),
           np.arange(1, 11, dtype=np.int64)]     # 10 -> chunked


def _model(kv_heads=2, seed=0):
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    paddle.seed(seed)
    cfg = llama_tiny_config(num_hidden_layers=2, hidden_size=64,
                            num_attention_heads=4,
                            num_key_value_heads=kv_heads,
                            vocab_size=128, intermediate_size=128)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model


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


def test_online_softmax_helper_byte_parity_and_stripe_merge():
    """Satellite 1: the extracted ``online_softmax_update`` must be
    BYTE-identical to the expression sequence both paged-attention
    kernels carried inline before round 22, and ``merge_partials`` over
    independently computed stripe partials must reproduce the one-pass
    softmax (empty stripes dropping out exactly)."""
    import jax.numpy as jnp
    from paddle_tpu.ops.online_softmax import (merge_partials,
                                               online_softmax_update)
    rng = np.random.default_rng(0)
    g, t, d = 8, 16, 32
    s = rng.standard_normal((g, t)).astype(np.float32) * 3.0
    ok = rng.random((g, t)) > 0.3
    ok[0] = False                                  # an all-masked row
    v = rng.standard_normal((t, d)).astype(np.float32)
    sm = jnp.where(jnp.asarray(ok), jnp.asarray(s), -jnp.inf)
    m0 = jnp.full((g, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((g, 1), jnp.float32)
    a0 = jnp.zeros((g, d), jnp.float32)

    # the pre-r22 inlined sequence, verbatim
    m_ref = jnp.maximum(m0, jnp.max(sm, axis=1, keepdims=True))
    p_ref = jnp.where(jnp.asarray(ok), jnp.exp(sm - m_ref),
                      np.float32(0.0))
    alpha = jnp.exp(m0 - m_ref)
    l_ref = l0 * alpha + jnp.sum(p_ref, axis=1, keepdims=True)
    a_ref = a0 * alpha + p_ref @ jnp.asarray(v)

    m1, l1, a1 = online_softmax_update(
        (m0, l0, a0), sm, jnp.asarray(ok), lambda p: p @ jnp.asarray(v))
    # equal_nan: the all-masked row carries -inf..-inf = NaN through
    # BOTH sequences identically (the kernels mask it downstream)
    assert np.array_equal(np.asarray(m1), np.asarray(m_ref))
    assert np.array_equal(np.asarray(l1), np.asarray(l_ref),
                          equal_nan=True)
    assert np.array_equal(np.asarray(a1), np.asarray(a_ref),
                          equal_nan=True)

    # stripe merge: split the score row in two halves computed
    # independently (each normalized), merge, compare to one softmax
    halves = []
    for sl in (slice(0, t // 2), slice(t // 2, t)):
        sh, okh, vh = sm[:, sl], jnp.asarray(ok[:, sl]), jnp.asarray(
            v[sl])
        mh = jnp.max(sh, axis=-1)
        msafe = jnp.where(jnp.isfinite(mh), mh, np.float32(0.0))
        ph = jnp.where(okh, jnp.exp(sh - msafe[:, None]),
                       np.float32(0.0))
        lh = jnp.sum(ph, axis=-1)
        oh = (ph @ vh) / jnp.maximum(lh, np.float32(1e-30))[:, None]
        halves.append((mh, lh, oh))
    mg = jnp.stack([h[0] for h in halves])
    lg = jnp.stack([h[1] for h in halves])
    og = jnp.stack([h[2] for h in halves])
    merged = merge_partials(mg, lg, og, axis=0)
    pfull = jnp.where(jnp.asarray(ok),
                      jnp.exp(sm - jnp.max(sm, axis=1, keepdims=True)),
                      np.float32(0.0))
    denom = jnp.sum(pfull, axis=1, keepdims=True)
    full = (pfull @ jnp.asarray(v)) / jnp.maximum(denom,
                                                  np.float32(1e-30))
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                               rtol=2e-6, atol=2e-6)
    # the all-masked row merges to exactly zero, never NaN
    assert np.array_equal(np.asarray(merged)[0], np.zeros((d,),
                                                          np.float32))


def test_cp2_mixed_parity_pool_stripe_and_compile_bound():
    """cp=2 fused mixed step: tokens byte-identical to the single-chip
    mixed engine under admission churn, per-chip KV-pool bytes exactly
    half (slot-striped pages), compiles bounded by the budget-set size,
    and the cp metrics published."""
    model = _model()
    e1, t1 = _run(model)
    e2, t2 = _run(model, mesh=cp_mesh(2))
    assert t2 == t1, "cp=2 tokens diverged from the single-chip step"
    assert e2.cp_degree == 2 and e2.tp_degree == 1
    assert set(e2.mixed.compile_counts) <= set(e2.token_budgets)
    assert all(v == 1 for v in e2.mixed.compile_counts.values())
    # slot-striped pools: per-chip bytes are EXACTLY 1/cp
    b1 = e1.caches[0].per_chip_pool_bytes()
    b2 = e2.caches[0].per_chip_pool_bytes()
    assert b2 * 2 == b1, (b1, b2)
    # no page leaks through the striped path
    assert len(e2.caches[0]._free) == 64
    # metrics: degree gauge + the stripe-merge byte counter
    from paddle_tpu.observability import default_registry
    r = default_registry()
    assert r.get("serving_cp_degree").value == 2.0
    counter = r.get("serving_cp_collective_bytes_total")
    assert counter.labels(op="all_gather").value > 0
    assert r.get("serving_mesh_shape").labels(axis="cp").value == 2.0


def test_cp_validation_errors_at_construction():
    """Invalid cp geometries must fail engine construction with an
    actionable message — not a shard_map shape error deep in tracing:
    a block_size that cp doesn't divide and int8 pools are both
    rejected."""
    model = _model()
    with pytest.raises(ValueError, match="divide"):
        ContinuousBatchingEngine(model, max_batch_size=2, num_blocks=16,
                                 block_size=6,
                                 prefill_chunk_size=4,
                                 mesh=cp_mesh(4))   # 6 % 4 != 0
    with pytest.raises(ValueError, match="int8"):
        ContinuousBatchingEngine(model, max_batch_size=2, num_blocks=16,
                                 block_size=4,
                                 prefill_chunk_size=4, kv_dtype="int8",
                                 mesh=cp_mesh(2))
    # cp=1 degenerates to the plain single-chip engine
    eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                   num_blocks=16, block_size=4,
                                   mesh=cp_mesh(1))
    assert eng.tp is None and eng.cp_degree == 1


@pytest.mark.slow
def test_cp4_mixed_parity():
    """cp=4 (block_size 4 stripes to one slot per chip): byte parity +
    compile bound + quarter pools."""
    model = _model()
    e1, t1 = _run(model)
    e4, t4 = _run(model, mesh=cp_mesh(4))
    assert t4 == t1
    assert e4.mixed.total_compiles <= len(e4.token_budgets)
    assert e4.caches[0].per_chip_pool_bytes() * 4 == \
        e1.caches[0].per_chip_pool_bytes()


@pytest.mark.slow
def test_cp2_tp2_composed_parity():
    """cp x tp on one 2x2 mesh: slot stripes compose with head shards —
    byte parity with single-chip, per-chip pool bytes exactly 1/4."""
    model = _model()
    e1, t1 = _run(model)
    ec, tc = _run(model, mesh=cp_mesh(2, tp=2))
    assert tc == t1
    assert ec.cp_degree == 2 and ec.tp_degree == 2
    assert ec.caches[0].per_chip_pool_bytes() * 4 == \
        e1.caches[0].per_chip_pool_bytes()


@pytest.mark.slow
def test_cp_slot_striped_pool_audit():
    """Each chip's pool shard must hold exactly its slot stripe of
    every page: layer-0 K/V (produced from bit-identical replicated
    activations) matches the single-chip pool bitwise; deeper layers to
    float tolerance (their inputs crossed the merge, which reorders
    float sums).  The sink page is excluded — under cp it absorbs the
    unowned-slot padding writes, which land differently than the
    single-chip sink garbage by design."""
    model = _model()
    e1, _ = _run(model)
    e2, _ = _run(model, mesh=cp_mesh(2))
    for li, (c1, c2) in enumerate(zip(e1.caches, e2.caches)):
        keep = np.arange(c2.key_cache.shape[0]) != c2.sink
        for a1, a2 in ((c1.key_cache, c2.key_cache),
                       (c1.value_cache, c2.value_cache)):
            full = np.asarray(a1)
            for shard in a2.addressable_shards:
                want = full[tuple(shard.index)][keep]
                got = np.asarray(shard.data)[keep]
                assert np.asarray(shard.data).shape[1] == \
                    c2.block_size // 2, "pool shard is not slot-striped"
                if li == 0:
                    np.testing.assert_array_equal(got, want)
                else:
                    np.testing.assert_allclose(got, want, rtol=2e-5,
                                               atol=2e-6)


@pytest.mark.slow
def test_cp_prefix_cache_cow_parity_and_leak_free():
    """Prefix-cache sharing and the whole-prompt-hit copy-on-write page
    copy must survive slot-striped pools (refcounts/COW/prefix keys are
    chip-local by design): byte parity, refcounts settle, no page
    leaked."""
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
    e2, t2 = run(cp_mesh(2))
    assert t2 == t1
    assert e2.finished[2].prefix_hit_tokens == 7      # COW capped hit
    pc = e2.prefix_cache
    cached = pc.cached_blocks()
    c0 = e2.caches[0]
    assert all(c0.refcount(b) == 1 for b in cached)
    assert len(c0._free) + len(cached) == c0.num_blocks


@pytest.mark.slow
def test_cp_chunked_long_prompt_parity():
    """A 20-token prompt prefills in chunks that cross page AND stripe
    boundaries (cp=4: one slot per chip per page)."""
    model = _model()
    long_prompts = [np.arange(1, 21, dtype=np.int64) % 120]

    def run_long(mesh):
        eng = ContinuousBatchingEngine(
            model, max_batch_size=4, num_blocks=64, block_size=4,
            prefill_chunk_size=4, mesh=mesh)
        rid = eng.add_request(long_prompts[0], 4)
        eng.run_to_completion()
        return eng.result(rid)

    assert run_long(cp_mesh(4)) == run_long(None)
