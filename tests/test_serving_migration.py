"""KV page migration + host-RAM prefix tier (round 19).

Tier-1 keeps to the fast lane: STUB-POOL tests only — raw
``PagedKVCache`` pools, no model, no engine compiles (the extract /
inject dispatches trace in milliseconds at toy shapes).  Everything
that builds a real engine — migrated-resume byte parity (fp32 and
int8), host-tier behavior under real admission pressure, the
disaggregated router flow — is @slow.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.prefix_cache import HostPageTier, PrefixPageCache
from paddle_tpu.jit.serving_step import (extract_blocks, inject_blocks,
                                         migration_compiles,
                                         migration_transfers)
from paddle_tpu.ops.paged_attention import PagedKVCache


def _pools(kv_dtype=None, layers=3, nb=8, bs=4, hkv=2, d=8):
    return [PagedKVCache(nb, bs, hkv, d, sink_block=True,
                         kv_dtype=kv_dtype) for _ in range(layers)]


def _fill(caches, ids, seed):
    """Write recognizable data into the given pages of every layer
    (host-side rebind — these pools never run a compiled step)."""
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    for c in caches:
        for name in ("key_cache", "value_cache"):
            arr = np.asarray(getattr(c, name)).copy()
            if c.quantized:
                arr[ids] = rng.randint(-127, 128, arr[ids].shape)
            else:
                arr[ids] = rng.randn(*arr[ids].shape)
            setattr(c, name, jnp.asarray(arr))
        if c.quantized:
            for name in ("key_scale", "value_scale"):
                arr = np.asarray(getattr(c, name)).copy()
                arr[ids] = rng.rand(*arr[ids].shape) + 0.1
                setattr(c, name, jnp.asarray(arr))


# ---------------------------------------------------------------------------
# tier-1: stub pools only
# ---------------------------------------------------------------------------
def test_extract_inject_round_trip_stub_pools():
    """The migration contract on raw pools: byte-exact round trip
    (fp32 AND int8 incl. scale rows), refcount-leak-free release,
    cross-kv_dtype injection rejected at construction, host-transfer
    count O(1) in the page count, and compiles bounded by geometry ×
    pow2 bucket (a repeat migration never re-traces)."""
    for kv_dtype in (None, "int8"):
        src = _pools(kv_dtype)
        dst = _pools(kv_dtype)
        ids = [src[0].allocate_block() for _ in range(3)]
        _fill(src, ids, seed=7)
        t0 = migration_transfers()
        buf = extract_blocks(src, ids, n_tokens=10)
        assert buf.n_pages == 3 and buf.n_tokens == 10
        assert buf.kv_dtype == src[0].kv_dtype

        dest = [dst[0].allocate_block() for _ in range(3)]
        inject_blocks(dst, buf, dest)
        t1 = migration_transfers()
        # O(1) payload copies per migration, NOT O(pages): 1 each way
        # for fp pools, 2 (codes + scales) for int8
        per_dir = 2 if kv_dtype == "int8" else 1
        assert t1["d2h"] - t0["d2h"] == per_dir
        assert t1["h2d"] - t0["h2d"] == per_dir

        for cs, cd in zip(src, dst):
            assert np.array_equal(np.asarray(cs.key_cache)[ids],
                                  np.asarray(cd.key_cache)[dest])
            assert np.array_equal(np.asarray(cs.value_cache)[ids],
                                  np.asarray(cd.value_cache)[dest])
            if kv_dtype == "int8":
                # per-page scale rows travel with their pages, so an
                # injected page dequantizes bit-identically
                assert np.array_equal(np.asarray(cs.key_scale)[ids],
                                      np.asarray(cd.key_scale)[dest])
                assert np.array_equal(np.asarray(cs.value_scale)[ids],
                                      np.asarray(cd.value_scale)[dest])

        # refcount audit: release everything through the ONE path —
        # free list returns to the full pool on both sides
        src[0].free_sequence(ids)
        dst[0].free_sequence(dest)
        assert len(src[0]._free) == src[0].num_blocks
        assert len(dst[0]._free) == dst[0].num_blocks
        assert src[0]._ref == {} and dst[0]._ref == {}

    # compile bound: a same-geometry repeat adds NO new traces
    src = _pools()
    dst = _pools()
    ids = [src[0].allocate_block() for _ in range(3)]
    _fill(src, ids, seed=9)
    buf = extract_blocks(src, ids, n_tokens=12)
    dest = [dst[0].allocate_block() for _ in range(3)]
    inject_blocks(dst, buf, dest)
    c0 = migration_compiles()
    buf2 = extract_blocks(src, ids, n_tokens=12)
    dest2 = [dst[0].allocate_block() for _ in range(3)]
    inject_blocks(dst, buf2, dest2)
    assert migration_compiles() == c0

    # cross-dtype injection: a clear construction error, never a
    # dtype/shape failure inside a trace
    q_src = _pools("int8")
    q_ids = [q_src[0].allocate_block() for _ in range(2)]
    _fill(q_src, q_ids, seed=11)
    q_buf = extract_blocks(q_src, q_ids, n_tokens=8)
    fp_dst = _pools()
    fp_dest = [fp_dst[0].allocate_block() for _ in range(2)]
    with pytest.raises(ValueError, match="kv_dtype"):
        inject_blocks(fp_dst, q_buf, fp_dest)
    # wrong destination count is also rejected before any side effect
    with pytest.raises(ValueError, match="destination"):
        inject_blocks(_pools("int8"), q_buf, [0])


def test_host_tier_spill_restore_stub_pools():
    """The spill tier on raw pools: eviction spills (one batched
    extract), a later match restores the chain byte-exactly (one
    batched inject), pinned entries are skipped AND counted, and the
    byte-capped LRU actually bounds the tier."""
    caches = _pools(layers=2, nb=4)
    tier = HostPageTier(1 << 20)
    pc = PrefixPageCache(caches[0], caches[0].block_size,
                         all_caches=caches, host_tier=tier)
    bs = caches[0].block_size
    rng = np.random.RandomState(3)
    prompt = rng.randint(1, 100, 2 * bs).astype(np.int64)
    ids = [caches[0].allocate_block() for _ in range(2)]
    _fill(caches, ids, seed=13)
    snap = [np.asarray(c.key_cache)[ids].copy() for c in caches]
    pc.register(prompt, ids)
    caches[0].free_sequence(ids)          # the request finished

    assert pc.evict(2) == 2
    assert pc.spills == 2 and len(tier) == 2
    assert len(caches[0]._free) == caches[0].num_blocks

    blocks = pc.match(prompt)             # restores out of the tier
    assert len(blocks) == 2
    assert pc.host_hits == 2 and pc.restores == 2 and len(tier) == 0
    for i, c in enumerate(caches):
        assert np.array_equal(snap[i], np.asarray(c.key_cache)[blocks])
    # the restored pages are table entries holding exactly one ref
    assert all(caches[0].refcount(b) == 1 for b in blocks)
    assert len(caches[0]._free) + len(pc.table) == caches[0].num_blocks

    # pinned entries are skipped and counted
    caches[0].share_blocks([blocks[0]])
    assert pc.evict(2) == 1
    assert pc.skipped_pinned == 1
    caches[0].free_sequence([blocks[0]])

    # byte cap: a tier sized for one page drops LRU entries on insert
    small = HostPageTier(snap[0][0:1].nbytes * 2 * len(caches) + 64)
    pc2 = PrefixPageCache(caches[0], bs, all_caches=caches,
                          host_tier=small)
    p2 = rng.randint(1, 100, 2 * bs).astype(np.int64)
    ids2 = [caches[0].allocate_block() for _ in range(2)]
    _fill(caches, ids2, seed=17)
    pc2.register(p2, ids2)
    caches[0].free_sequence(ids2)
    pc2.evict(2)
    assert len(small) == 1 and small.tier_evictions == 1
    assert small.bytes <= small.capacity_bytes


# ---------------------------------------------------------------------------
# slow lane: real engines
# ---------------------------------------------------------------------------
def _tiny_model():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    paddle.seed(0)
    cfg = llama_tiny_config()
    model = LlamaForCausalLM(cfg)
    model.eval()
    return cfg, model


def _engine(model, **kw):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk_size", 8)
    kw.setdefault("enable_prefix_cache", True)
    return ContinuousBatchingEngine(model, **kw)


def _leak_free(eng):
    c0 = eng.caches[0]
    cached = eng.prefix_cache.cached_blocks()
    return (len(c0._free) + len(cached) == c0.num_blocks
            and all(c0.refcount(b) == 1 for b in cached))


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_migrated_resume_stream_parity(kv_dtype):
    """extract_request → inject_request across two engines: the
    migrated greedy stream is byte-identical to the uninterrupted
    single-engine run (fp32 bit-exact KV; int8 codes + scales copied
    exactly, so attention reads the same numbers), and both pools end
    leak-free."""
    cfg, model = _tiny_model()
    rng = np.random.RandomState(5)
    prompt = rng.randint(1, cfg.vocab_size, (9,)).astype(np.int64)
    budget = 8

    e_ref = _engine(model, kv_dtype=kv_dtype)
    rid = e_ref.add_request(prompt, max_new_tokens=budget)
    ref = e_ref.run_to_completion()[rid]

    ea = _engine(model, kv_dtype=kv_dtype)
    eb = _engine(model, kv_dtype=kv_dtype)
    rid = ea.add_request(prompt, max_new_tokens=budget)
    for _ in range(4):
        ea.step()
    p, gen, buf = ea.extract_request(rid)
    assert buf is not None and 0 < len(gen) < budget
    assert buf.n_tokens == len(p) + len(gen) - 1
    resume = np.concatenate([p, np.asarray(gen, np.int64)])
    rid2 = eb.inject_request(resume, buf,
                             max_new_tokens=budget - len(gen))
    out = eb.run_to_completion()[rid2]
    assert gen + out == ref
    assert _leak_free(ea) and _leak_free(eb)

    # the injected pages re-registered under the digest chain: a
    # same-prefix admission on the TARGET engine hits
    h0 = eb.prefix_cache.hits
    rid3 = eb.add_request(resume[:8], max_new_tokens=2)
    eb.run_to_completion()
    assert eb.prefix_cache.hits == h0 + 1


@pytest.mark.slow
def test_inject_request_validation():
    """inject_request's fallback contract: ValueError for requests the
    engine can never hold, RuntimeError for transient capacity — both
    BEFORE any side effect (the pool state is untouched)."""
    cfg, model = _tiny_model()
    rng = np.random.RandomState(6)
    prompt = rng.randint(1, cfg.vocab_size, (9,)).astype(np.int64)
    ea = _engine(model)
    rid = ea.add_request(prompt, max_new_tokens=8)
    for _ in range(3):
        ea.step()
    p, gen, buf = ea.extract_request(rid)
    resume = np.concatenate([p, np.asarray(gen, np.int64)])

    e8 = _engine(model, kv_dtype="int8")
    free_before = len(e8.caches[0]._free)
    with pytest.raises(ValueError, match="kv_dtype"):
        e8.inject_request(resume, buf, max_new_tokens=4)
    assert len(e8.caches[0]._free) == free_before

    eb = _engine(model)
    with pytest.raises(ValueError, match="n_tokens"):
        eb.inject_request(resume[:-1], buf, max_new_tokens=4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eb.inject_request(resume, buf, max_new_tokens=0)

    # no free slot -> RuntimeError (transient), pool untouched
    ec = _engine(model, max_batch_size=1)
    ec.add_request(rng.randint(1, cfg.vocab_size, (9,)).astype(np.int64),
                   max_new_tokens=16)
    ec.step()
    free_before = len(ec.caches[0]._free)
    with pytest.raises(RuntimeError, match="free slot"):
        ec.inject_request(resume, buf, max_new_tokens=4)
    assert len(ec.caches[0]._free) == free_before


@pytest.mark.slow
def test_host_tier_hit_rate_under_pressure():
    """Same workload, same HBM cap: the second wave's prefix hit rate
    with the host tier strictly beats without it, outputs stay parity
    with the eager reference, and the pool ends leak-free."""
    cfg, model = _tiny_model()
    rng = np.random.RandomState(7)
    families = [rng.randint(1, cfg.vocab_size, (8,)).astype(np.int64)
                for _ in range(4)]
    suffixes = [rng.randint(1, cfg.vocab_size, (4, 3)).astype(np.int64)
                for _ in range(2)]

    def run_wave(eng, wave):
        outs = []
        for i, fam in enumerate(families):
            prompt = np.concatenate([fam, suffixes[wave][i]])
            rid = eng.add_request(prompt, max_new_tokens=4)
            eng.run_to_completion()
            outs.append((prompt, eng.finished[rid].output_ids))
        return outs

    results = {}
    for tier in (1 << 22, 0):
        eng = _engine(model, num_blocks=6, max_seq_len=16,
                      host_tier_bytes=tier)
        run_wave(eng, 0)
        h0, m0 = eng.prefix_cache.hits, eng.prefix_cache.misses
        outs = run_wave(eng, 1)
        h1, m1 = eng.prefix_cache.hits, eng.prefix_cache.misses
        results[tier] = (h1 - h0) / max(1, (h1 - h0) + (m1 - m0))
        if tier:
            assert eng.prefix_cache.spills > 0
            assert eng.prefix_cache.restores > 0
            payload = eng.health_payload()
            assert payload["host_tier_entries"] == len(eng.host_tier)
            assert payload["host_tier_bytes"] == eng.host_tier.bytes
        assert _leak_free(eng)
        # restored-prefix streams match the eager reference
        for prompt, out in outs[:2]:
            ref = model.generate(
                paddle.to_tensor(np.asarray(prompt)[None, :]),
                max_new_tokens=4)
            assert out == np.asarray(
                ref._value)[0, len(prompt):].tolist()
    assert results[1 << 22] > results[0]


@pytest.mark.slow
def test_disagg_router_prefill_to_decode_migration():
    """A prefill-specialist + decode-specialist pool: fresh prompts
    land on the prefill engine, their pages migrate after the first
    token, streams stay byte-identical to the eager reference, and
    the round-16 span-chain contract holds across the migration hop."""
    from paddle_tpu.inference.router import ServingRouter
    from paddle_tpu.observability.request_trace import validate_span_chain
    cfg, model = _tiny_model()
    rng = np.random.RandomState(8)
    pe = _engine(model, role="prefill", engine_id=1900)
    de = _engine(model, max_batch_size=4, role="decode",
                 engine_id=1901)
    router = ServingRouter([pe, de])
    prompts = [rng.randint(1, cfg.vocab_size, (9,)).astype(np.int64)
               for _ in range(3)]
    rids = [router.submit(p, max_new_tokens=8) for p in prompts]
    out = router.run_to_completion()
    for rid, p in zip(rids, prompts):
        ref = model.generate(paddle.to_tensor(np.asarray(p)[None, :]),
                             max_new_tokens=8)
        assert out[rid] == np.asarray(ref._value)[0, len(p):].tolist()
    migrated = [r for r in rids
                if router.finished[r].engines_visited()[0] == 1900]
    assert migrated, "no request ever started on the prefill tier"
    for r in migrated:
        rr = router.finished[r]
        assert rr.migrations >= 1
        assert rr.engines_visited()[-1] == 1901
        assert rr.summary["migrations"] == rr.migrations
    for rid in rids:
        ok, why = validate_span_chain(router.tracer.events(rid))
        assert ok, (rid, why)
    assert _leak_free(pe) and _leak_free(de)


@pytest.mark.slow
def test_router_drain_resumes_via_inject():
    """Engine loss mid-decode: the drain extracts the victims' pages
    and the re-dispatch INJECTS them (the dispatch span says
    migrated=True) — zero drops, byte-identical streams, zero
    re-prefill on the resume path."""
    from paddle_tpu.inference.router import ServingRouter
    cfg, model = _tiny_model()
    rng = np.random.RandomState(9)
    e1 = _engine(model, engine_id=1910)
    e2 = _engine(model, engine_id=1911)
    router = ServingRouter([e1, e2])
    prompts = [rng.randint(1, cfg.vocab_size, (9,)).astype(np.int64)
               for _ in range(3)]
    rids = [router.submit(p, max_new_tokens=8) for p in prompts]
    for _ in range(4):
        router.step()
    per = {}
    for (eid, _erid) in router._inflight:
        per[eid] = per.get(eid, 0) + 1
    victim_id = max(per, key=per.get)
    victim = router.handles[victim_id].engine

    def _dead():
        raise RuntimeError("injected engine loss")
    victim.step = _dead
    out = router.run_to_completion()
    injected_resumes = 0
    for rid, p in zip(rids, prompts):
        ref = model.generate(paddle.to_tensor(np.asarray(p)[None, :]),
                             max_new_tokens=8)
        assert out[rid] == np.asarray(ref._value)[0, len(p):].tolist()
        for ev in router.tracer.events(rid):
            if ev[1] == "dispatch" and ev[-1].get("migrated"):
                injected_resumes += 1
    assert injected_resumes >= 1, \
        "drain fell back to re-prefill for every victim"
    survivor = e2 if victim is e1 else e1
    assert _leak_free(survivor)
