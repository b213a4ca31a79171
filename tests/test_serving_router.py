"""Multi-engine serving router (round 15): prefix-affinity admission
plane with drain-and-requeue.

Tier-1 keeps to the fast lane: routing-DECISION unit tests run against
in-process stub engines (pure host control flow, no model, no
compiles), plus ONE two-engine requeue parity test on the tiny llama.
The heavyweight drills (e2e kill with mixed/prefix engines, preempt
under COW sharing, the heterogeneous tp+quant pool) are @slow.
"""
import types

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.router import (EngineHandle, RouterQueueFull,
                                         ServingRouter, load_score,
                                         routing_keys)


# ---------------------------------------------------------------------------
# stub engines: the minimal engine protocol, deterministic, no device
# ---------------------------------------------------------------------------
class _StubReq:
    def __init__(self, rid, prompt, budget):
        self.req_id = rid
        self.prompt_ids = np.asarray(prompt, np.int64)
        self.output_ids = []
        self.max_new_tokens = budget
        self.t_first_token = 0.0
        self.truncated = False
        self.slot = -1                # -1 while waiting (engine parity)


class _StubEngine:
    """Admits up to `slots` requests, emits one fixed token per running
    request per step; prefix table + free pages are plain knobs so
    routing decisions are directly controllable."""
    block_size = 4

    def __init__(self, engine_id, slots=1, prefix_keys=(),
                 free_pages=100, max_prompt=None):
        self.engine_id = engine_id
        self.max_batch_size = slots
        self.max_prompt = max_prompt
        self.waiting = []
        self.running = []
        self.finished = {}
        self.admitted = []            # req_ids in admission order
        self.free_pages = free_pages
        self.prefix_cache = types.SimpleNamespace(
            table={k: 0 for k in prefix_keys})
        self._next = 0

    def add_request(self, prompt_ids, max_new_tokens=16,
                    eos_token_id=None):
        if self.max_prompt is not None \
                and len(prompt_ids) > self.max_prompt:
            raise ValueError("prompt too long for this engine")
        r = _StubReq(self._next, prompt_ids, max_new_tokens)
        self._next += 1
        self.waiting.append(r)
        return r.req_id

    def has_work(self):
        return bool(self.waiting or self.running)

    def step(self):
        while self.waiting and len(self.running) < self.max_batch_size:
            r = self.waiting.pop(0)
            r.slot = len(self.running)
            self.running.append(r)
            self.admitted.append(r.req_id)
        done = []
        for r in list(self.running):
            r.output_ids.append(7)
            if len(r.output_ids) >= r.max_new_tokens:
                self.running.remove(r)
                self.finished[r.req_id] = r
                done.append(r.req_id)
        return done

    def preempt_request(self, rid):
        for q in (self.waiting, self.running):
            for r in list(q):
                if r.req_id == rid:
                    q.remove(r)
                    r.slot = -1
                    return r.prompt_ids, list(r.output_ids)
        raise KeyError(rid)

    def health_payload(self):
        return {"engine_id": self.engine_id,
                "occupancy": len(self.running),
                "slots": self.max_batch_size,
                "waiting": len(self.waiting),
                "free_pages": self.free_pages, "total_pages": 100,
                "chunk_queue_depth": 0}


def test_routing_key_and_load_score():
    """routing_keys == the PrefixPageCache digest chain; load_score is
    monotone in each pressure axis."""
    from paddle_tpu.inference.prefix_cache import _prefix_key
    P = np.arange(1, 11, dtype=np.int64)          # 10 tokens, bs 4
    keys = routing_keys(P, 4)
    assert keys == [_prefix_key(P, 4), _prefix_key(P, 8)]
    idle = {"occupancy": 0, "slots": 4, "waiting": 0,
            "free_pages": 100, "total_pages": 100,
            "chunk_queue_depth": 0}
    assert load_score(idle) == 0.0
    for k, v in (("occupancy", 2), ("waiting", 1),
                 ("free_pages", 10), ("chunk_queue_depth", 3)):
        assert load_score({**idle, k: v}) > 0.0
    assert load_score({}) == 0.0                  # thin payloads route


def test_affinity_pick_beats_load_and_falls_back_least_loaded():
    """A prompt whose prefix pages live on a busier engine still routes
    there; a no-match prompt goes least-loaded."""
    P = np.arange(1, 13, dtype=np.int64)
    keys = routing_keys(P, 4)
    e0 = _StubEngine(0, slots=4, prefix_keys=keys[:2], free_pages=20)
    e1 = _StubEngine(1, slots=4, free_pages=100)   # emptier, no prefix
    router = ServingRouter([e0, e1])
    a = router.submit(P, max_new_tokens=1)
    router.step()
    assert e0.admitted and not e1.admitted        # affinity won
    assert router.finished[a].routed_by_prefix
    # no-match prompt: least-loaded fallback picks the emptier engine
    q = np.arange(50, 62, dtype=np.int64)
    b = router.submit(q, max_new_tokens=1)
    router.step()
    assert e1.admitted
    assert not router.finished[b].routed_by_prefix


def test_affinity_holds_for_full_engine_then_spills():
    """A matching request HOLDS while its affinity target is full
    (bounded), instead of instantly recomputing the prefix elsewhere."""
    P = np.arange(1, 13, dtype=np.int64)
    keys = routing_keys(P, 4)
    e0 = _StubEngine(0, slots=1, prefix_keys=keys)
    e1 = _StubEngine(1, slots=1)
    router = ServingRouter([e0, e1], affinity_wait_steps=100)
    blocker = router.submit(np.arange(90, 94, dtype=np.int64),
                            max_new_tokens=5)
    router.step()                                  # blocker runs on e0?
    # force the blocker onto e0 regardless of tie-breaks
    if not e0.running:
        e0, e1 = e1, e0
    hit = router.submit(P, max_new_tokens=1)
    router.step()
    assert router.pending and router.pending[0].rid == hit  # holding
    assert not e1.admitted or e1.admitted == []   # never spilled
    router.run_to_completion()
    assert router.finished[hit].routed_by_prefix
    assert router.finished[blocker].requeues == 0  # equal pri: no preempt


def test_priority_order_and_preempt_requeue():
    """Admission drains highest-priority-first; a high-priority request
    preempts a strictly-lower-priority running one through the public
    preempt API, and the victim resumes with its tokens re-prefixed."""
    e = _StubEngine(0, slots=1)
    router = ServingRouter([e])
    lo = router.submit(np.arange(4, dtype=np.int64), max_new_tokens=6,
                       priority=0)
    router.step()                                 # lo runs, has 1 token
    mid = router.submit(np.arange(8, 12, dtype=np.int64),
                        max_new_tokens=1, priority=2)
    hi = router.submit(np.arange(20, 24, dtype=np.int64),
                       max_new_tokens=1, priority=5)
    router.step()
    # hi preempted lo (never mid: it outranks lo only), lo is pending
    assert [rr.rid for rr in router.pending if rr.rid == lo]
    assert all(rr.rid != hi for rr in router.pending)   # hi dispatched
    out = router.run_to_completion()
    f_lo = router.finished[lo]
    assert f_lo.requeues == 1
    # the victim's pre-preemption token was re-prefixed, not lost:
    # total output still exactly its budget
    assert len(out[lo]) == 6
    assert len(out[hi]) == 1 and len(out[mid]) == 1
    # hi admitted before mid, mid before lo's re-admission
    order = e.admitted
    assert order.index(router.finished[hi].engine_req_id) \
        < order.index(router.finished[mid].engine_req_id)


def test_tpot_target_shields_victim_and_ttft_zero_is_urgent():
    """Among equal-priority victims the one WITHOUT a TPOT target is
    preempted; ttft_target=0.0 means maximal urgency, not 'no
    deadline'."""
    e = _StubEngine(0, slots=2)
    router = ServingRouter([e])
    slo = router.submit(np.arange(4, dtype=np.int64), max_new_tokens=8,
                        priority=0, tpot_target=0.01)
    free = router.submit(np.arange(8, 12, dtype=np.int64),
                         max_new_tokens=8, priority=0)
    router.step()
    hi = router.submit(np.arange(20, 24, dtype=np.int64),
                       max_new_tokens=1, priority=5)
    router.step()
    # the no-target request was the victim, the TPOT-target one kept
    # its slot
    reqs = {rr.rid: rr for rr in router.pending}
    assert free in reqs and slo not in reqs
    router.run_to_completion()
    assert len(router.result(free)) == 8 and len(router.result(slo)) == 8
    assert len(router.result(hi)) == 1
    # ttft_target=0.0 sorts AHEAD of an unconstrained equal-priority
    # peer (deadline=now vs inf)
    a = router.submit(np.arange(4, dtype=np.int64), max_new_tokens=1)
    b = router.submit(np.arange(4, dtype=np.int64), max_new_tokens=1,
                      ttft_target=0.0)
    router.run_to_completion()
    order = e.admitted
    assert order.index(router.finished[b].engine_req_id) \
        < order.index(router.finished[a].engine_req_id)


def test_bounded_queue_and_health_gauge():
    e = _StubEngine(0, slots=1)
    router = ServingRouter([e], max_pending=1)
    router.submit(np.arange(4, dtype=np.int64), max_new_tokens=2)
    with pytest.raises(RouterQueueFull):
        router.submit(np.arange(4, dtype=np.int64), max_new_tokens=2)
    router.step()          # dispatch + first token, request in flight
    # probe failure (payload raises) drains the engine and zeroes the
    # health gauge; recover_engine re-admits
    def _boom():
        raise OSError("probe down")
    e.health_payload = _boom
    router.step()
    h = router.handles[0]
    assert not h.healthy
    assert router.pending and router.pending[0].requeues == 1
    e.health_payload = lambda: {"slots": 1}
    router.recover_engine(0)
    assert router.handles[0].healthy
    out = router.run_to_completion()
    assert all(len(v) == 2 for v in out.values())


def test_out_of_band_completion_surfaces_in_next_step():
    """A request completed during a drain (engine died with the final
    token already in its host state) must show up in step()'s returned
    rid list — never silently land only in `finished`."""
    e = _StubEngine(0, slots=1)
    router = ServingRouter([e])
    a = router.submit(np.arange(4, dtype=np.int64), max_new_tokens=2)
    router.step()                       # one token, in flight
    rr = next(iter(router._inflight.values()))
    rr.engine_req.output_ids.append(7)  # budget met inside the dying step
    def _dead():
        raise RuntimeError("boom")
    def _gone(rid):
        raise KeyError(rid)             # raced with completion
    e.step = _dead
    e.preempt_request = _gone
    done = router.step()                # drain -> out-of-band complete
    assert done == [a]
    assert router.result(a) == [7, 7]


def test_unplaceable_request_never_preempts():
    """A request no engine's geometry can hold must not churn running
    victims through pointless preemptions; run_to_completion fails
    loudly once nothing else is in flight."""
    e0 = _StubEngine(0, slots=1, max_prompt=4)
    e1 = _StubEngine(1, slots=1, max_prompt=4)
    router = ServingRouter([e0, e1])
    lo = router.submit(np.arange(4, dtype=np.int64), max_new_tokens=3,
                       priority=0)
    router.step()
    big = router.submit(np.arange(10, dtype=np.int64), max_new_tokens=2,
                        priority=9)
    for _ in range(2):
        router.step()
    assert router.finished.get(lo) is None \
        or router.finished[lo].requeues == 0
    out_lo = router.finished.get(lo)
    with pytest.raises(RuntimeError, match="fit no engine"):
        router.run_to_completion()
    assert router.finished[lo].requeues == 0      # victim untouched
    assert len(router.finished[lo].output_ids) == 3
    assert big not in router.finished
    del out_lo


def test_affinity_geometry_rejection_reranks_before_preempting():
    """The affinity engine matching a prompt rejects it on geometry:
    the request must re-rank onto another engine's FREE slot, never
    preempt a victim while open capacity exists."""
    P = np.arange(1, 13, dtype=np.int64)
    a = _StubEngine(0, slots=2, prefix_keys=routing_keys(P, 4),
                    max_prompt=4)          # matches, but can't hold P
    b = _StubEngine(1, slots=2)            # free slot + a running victim
    router = ServingRouter([a, b])
    lo = router.submit(np.arange(90, 94, dtype=np.int64),
                       max_new_tokens=6, priority=0)
    router.step()
    hi = router.submit(P, max_new_tokens=2, priority=5)
    out = router.run_to_completion()
    assert len(out[hi]) == 2 and len(out[lo]) == 6
    assert router.finished[lo].requeues == 0     # victim untouched
    assert router.finished[hi].engine_id == 1    # spilled to b's slot


def test_finished_retention_pop_result_and_anonymous_engines():
    """finished is a bounded record (oldest evicted past max_finished,
    pop_result consumes); engines without an engine_id attribute get
    distinct fallback ids instead of colliding at 0."""
    class _Anon(_StubEngine):
        def __init__(self, slots):
            super().__init__(0, slots=slots)
            del self.engine_id         # protocol-minimal pool member

        def health_payload(self):
            return {"occupancy": len(self.running),
                    "slots": self.max_batch_size,
                    "waiting": len(self.waiting),
                    "free_pages": 100, "total_pages": 100,
                    "chunk_queue_depth": 0}
    e0, e1 = _Anon(slots=2), _Anon(slots=2)
    router = ServingRouter([e0, e1], max_finished=2)
    assert len(router.handles) == 2    # distinct fallback ids
    rids = [router.submit(np.arange(4, dtype=np.int64),
                          max_new_tokens=1) for _ in range(3)]
    router.run_to_completion()
    assert len(router.finished) == 2
    assert rids[0] not in router.finished      # oldest evicted
    assert router.pop_result(rids[2]) == [7]
    assert rids[2] not in router.finished
    # the router consumed the ENGINE-side records too — neither layer
    # retains per-request state without bound
    assert not e0.finished and not e1.finished


def test_healthz_payload_merge_keeps_bare_contract():
    """/healthz body: status ok always; provider dict merged; a broken
    provider degrades to the bare payload instead of failing a probe."""
    from paddle_tpu.observability.exporters import healthz_payload
    assert healthz_payload() == {"status": "ok"}
    body = healthz_payload(lambda: {"engine_id": 3, "occupancy": 1,
                                    "status": "evil"})
    assert body["status"] == "ok"                 # liveness field ours
    assert body["engine_id"] == 3 and body["occupancy"] == 1
    def _boom():
        raise RuntimeError("stats broke")
    assert healthz_payload(_boom) == {"status": "ok"}


# ---------------------------------------------------------------------------
# real engines
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


def _ref_tokens(model, prompt, n):
    out = model.generate(paddle.to_tensor(np.asarray(prompt)[None, :]),
                         max_new_tokens=n)
    return np.asarray(out._value)[0, len(prompt):].tolist()


def test_two_engine_requeue_parity():
    """Engine lost mid-decode: every in-flight request drains off and
    resumes on the survivor byte-identical to an uninterrupted greedy
    run, and the drained engine's pool is fully released."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    e1 = ContinuousBatchingEngine(model, max_batch_size=2,
                                  num_blocks=32, block_size=4)
    e2 = ContinuousBatchingEngine(model, max_batch_size=2,
                                  num_blocks=32, block_size=4)
    router = ServingRouter([e1, e2])
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 128, (n,)).astype(np.int64)
               for n in (5, 7, 4)]
    rids = [router.submit(p, max_new_tokens=4) for p in prompts]
    for _ in range(2):
        router.step()
    lost = sum(1 for k in router._inflight if k[0] == e1.engine_id)
    assert lost >= 1                 # the kill actually hits live work
    router.mark_unhealthy(e1.engine_id)
    out = router.run_to_completion()
    for rid, p in zip(rids, prompts):
        assert out[rid] == _ref_tokens(model, p, 4)
    assert sum(router.finished[r].requeues for r in rids) == lost
    assert all(len(out[r]) == 4 for r in rids)    # zero drops, full runs
    c = e1.caches[0]
    assert len(c._free) == c.num_blocks           # drained leak-free
    # requeue metric counted under engine_lost
    reqs = router._m_requeues.labels(reason="engine_lost")
    assert reqs.value >= lost
    # regression: a request that completes DURING admission (budget 1,
    # dense prefill) must surface in step()'s return — the router keys
    # on it (it used to go missing and wedge run_to_completion).  The
    # call that launches the prefill returns before its token is
    # fetched; the next call books it and returns the id
    rid1 = e2.add_request(prompts[0], max_new_tokens=1)
    assert rid1 not in e2.step()
    assert rid1 in e2.step()
    r1 = router.submit(prompts[1], max_new_tokens=1)
    assert router.run_to_completion()[r1] \
        == _ref_tokens(model, prompts[1], 1)


# ---------------------------------------------------------------------------
# slow lane: e2e drills
# ---------------------------------------------------------------------------
def _mk_prefix_engine(model, **kw):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk_size", 8)
    kw.setdefault("enable_prefix_cache", True)
    return ContinuousBatchingEngine(model, **kw)


@pytest.mark.slow
def test_kill_drill_mixed_prefix_engines_and_recovery():
    """Bench drill in-suite: a mixed-step/prefix-cache engine's step()
    starts raising mid-run; zero drops, byte parity, drained pool
    leak-free — then the engine RECOVERS and serves again."""
    model = _tiny_model()
    e1, e2 = _mk_prefix_engine(model), _mk_prefix_engine(model)
    router = ServingRouter([e1, e2])
    rng = np.random.RandomState(11)
    prefix = rng.randint(1, 128, (12,)).astype(np.int64)
    prompts = [np.concatenate([prefix,
                               rng.randint(1, 128, (4,)).astype(np.int64)])
               for _ in range(5)]
    prompts += [rng.randint(1, 128, (9,)).astype(np.int64)]
    rids = [router.submit(p, max_new_tokens=6) for p in prompts]
    for _ in range(3):
        router.step()
    victim = e1 if any(k[0] == e1.engine_id for k in router._inflight) \
        else e2
    real_step = victim.step
    def _dead():
        raise RuntimeError("injected loss")
    victim.step = _dead
    out = router.run_to_completion()
    for rid, p in zip(rids, prompts):
        assert out[rid] == _ref_tokens(model, p, 6), rid
    assert sum(router.finished[r].requeues for r in rids) >= 1
    c0 = victim.caches[0]
    cached = victim.prefix_cache.cached_blocks()
    assert len(c0._free) + len(cached) == c0.num_blocks
    assert all(c0.refcount(b) == 1 for b in cached)
    # recovery: the engine comes back and serves new work
    victim.step = real_step
    router.recover_engine(victim.engine_id)
    extra = router.submit(prompts[0], max_new_tokens=4)
    out2 = router.run_to_completion()
    assert out2[extra] == _ref_tokens(model, prompts[0], 4)


@pytest.mark.slow
def test_preempt_under_cow_and_int8_scale_pages_leak_free():
    """preempt_request audit under prefix-COW sharing and int8
    scale-carrying pages: releasing a preempted request never strands
    or double-frees a page; the survivor's tokens stay byte-identical."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    for kv_dtype in (None, "int8"):
        eng = ContinuousBatchingEngine(
            model, max_batch_size=2, num_blocks=32, block_size=4,
            prefill_chunk_size=8,
            enable_prefix_cache=True, kv_dtype=kv_dtype)
        P = np.array([5, 17, 42, 7, 99, 3, 11, 23], np.int64)
        ra = eng.add_request(P, 8)
        eng.run_to_completion()
        want_a = eng.result(ra)
        # B: whole-prompt hit -> COW page; C shares the prefix pages
        rb = eng.add_request(P, 8)
        rc = eng.add_request(np.concatenate([P, [77, 8]]), 8)
        eng.step()
        eng.step()
        prompt_b, gen_b = eng.preempt_request(rb)
        assert np.array_equal(prompt_b, P) and len(gen_b) >= 1
        # the preempted share died; pages shared with the table/C live
        eng.run_to_completion()
        # resume B on a second engine with tokens re-prefixed
        eng2 = ContinuousBatchingEngine(
            model, max_batch_size=2, num_blocks=32, block_size=4,
            prefill_chunk_size=8,
            enable_prefix_cache=True, kv_dtype=kv_dtype)
        rb2 = eng2.add_request(np.concatenate([P, gen_b]),
                               8 - len(gen_b))
        eng2.run_to_completion()
        if kv_dtype is None:
            assert gen_b + eng2.result(rb2) == want_a
        else:
            assert len(gen_b) + len(eng2.result(rb2)) == 8
        for e in (eng, eng2):
            c0 = e.caches[0]
            cached = e.prefix_cache.cached_blocks()
            assert len(c0._free) + len(cached) == c0.num_blocks
            assert all(c0.refcount(b) == 1 for b in cached)


@pytest.mark.slow
def test_heterogeneous_pool_tp_plus_quant_routing():
    """One admission plane over a heterogeneous pool: a tensor-parallel
    tp=2 engine and an int8-KV engine.  Affinity co-locates a shared-
    prefix family, everything completes, and the tp engine's outputs
    stay byte-identical to eager."""
    from paddle_tpu.distributed import ProcessMesh
    from paddle_tpu.testing.dryrun import force_cpu_devices
    force_cpu_devices(8)
    model = _tiny_model()
    mesh = ProcessMesh(shape=[2], dim_names=["tp"])
    e_tp = _mk_prefix_engine(model, mesh=mesh)
    e_q8 = _mk_prefix_engine(model, kv_dtype="int8")
    router = ServingRouter([e_tp, e_q8])
    rng = np.random.RandomState(13)
    prefix = rng.randint(1, 128, (12,)).astype(np.int64)
    fam = [np.concatenate([prefix,
                           rng.randint(1, 128, (4,)).astype(np.int64)])
           for _ in range(3)]
    lone = [rng.randint(1, 128, (n,)).astype(np.int64) for n in (6, 10)]
    rids = {router.submit(p, max_new_tokens=5): p for p in fam + lone}
    out = router.run_to_completion()
    assert set(out) == set(rids) and all(len(v) == 5
                                         for v in out.values())
    # the family co-located on ONE engine (the router property; two
    # siblings admitted in the same engine round can still miss the
    # registration window, so hit COUNT is engine timing, >= 1 here)
    fam_rids = [rid for rid, p in rids.items()
                if len(p) == 16 and np.array_equal(p[:12], prefix)]
    fam_engines = {router.finished[rid].engine_id for rid in fam_rids}
    assert len(fam_engines) == 1
    assert e_tp.prefix_cache.hits + e_q8.prefix_cache.hits >= 1
    # byte parity for everything the tp (exact-math) engine served
    for rid, rr in router.finished.items():
        if rr.engine_id == e_tp.engine_id:
            assert out[rid] == _ref_tokens(model, rids[rid], 5)


@pytest.mark.slow
def test_engine_handle_scrapes_healthz_http():
    """EngineHandle(health_url=...) reads load from the upgraded
    /healthz JSON body — no Prometheus text parsing."""
    from paddle_tpu.observability import MetricsServer
    e = _StubEngine(0, slots=3)
    e.add_request(np.arange(4, dtype=np.int64), max_new_tokens=99)
    e.step()
    # numpy scalars in the payload must not break the endpoint (the
    # handler serializes with default=str and falls back to bare-ok)
    provider = lambda: {**e.health_payload(),          # noqa: E731
                        "np_field": np.int64(3)}
    srv = MetricsServer(port=0, addr="127.0.0.1",
                        health_provider=provider).start()
    try:
        h = EngineHandle(e, health_url="http://127.0.0.1:%d/healthz"
                                       % srv.port)
        p = h.payload()
        assert p["status"] == "ok" and p["occupancy"] == 1
        assert p["slots"] == 3 and p["engine_id"] == 0
        assert h.probe() and load_score(p) > 0.0
    finally:
        srv.stop()
