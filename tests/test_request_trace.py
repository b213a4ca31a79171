"""Fleet request tracing + SLO attainment (round 16).

Tier-1 keeps to the fast lane: tracer-unit tests plus span-chain /
SLO-arithmetic / fleet-trace checks against in-process STUB engines
(pure host control flow, no model, no compiles).  The real-engine e2e
kill-drill trace (mixed+prefix engines, byte parity, gap-free chains
across a live requeue) is @slow — tier-1 sits AT the 870s budget.
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import (NULL_TRACER, LatencyReservoir,
                                      RequestTracer, fleet_trace,
                                      resolve_tracer,
                                      validate_span_chain)
from paddle_tpu.inference.router import ServingRouter


# ---------------------------------------------------------------------------
# stub engine: the minimal engine protocol, with a tracer of its own
# (the real engine's default-ON contract) so fleet_trace has engine
# lanes to merge
# ---------------------------------------------------------------------------
class _StubReq:
    def __init__(self, rid, prompt, budget):
        self.req_id = rid
        self.prompt_ids = np.asarray(prompt, np.int64)
        self.output_ids = []
        self.max_new_tokens = budget
        self.t_first_token = 0.0
        self.truncated = False
        self.slot = -1


class _StubEngine:
    block_size = 4

    def __init__(self, engine_id, slots=1):
        self.engine_id = engine_id
        self.max_batch_size = slots
        self.waiting = []
        self.running = []
        self.finished = {}
        self.prefix_cache = None
        self.tracer = RequestTracer()
        self._next = 0

    def add_request(self, prompt_ids, max_new_tokens=16,
                    eos_token_id=None):
        r = _StubReq(self._next, prompt_ids, max_new_tokens)
        self._next += 1
        self.waiting.append(r)
        self.tracer.event(r.req_id, "enqueue")
        return r.req_id

    def has_work(self):
        return bool(self.waiting or self.running)

    def step(self):
        import time
        while self.waiting and len(self.running) < self.max_batch_size:
            r = self.waiting.pop(0)
            r.slot = len(self.running)
            self.running.append(r)
        done = []
        t = time.perf_counter()
        for r in list(self.running):
            r.output_ids.append(7)
            if len(r.output_ids) == 1:
                r.t_first_token = t
            self.tracer.sample_span(r.req_id, "decode_step",
                                    t - 1e-4, t, every=1)
            if len(r.output_ids) >= r.max_new_tokens:
                self.running.remove(r)
                self.finished[r.req_id] = r
                self.tracer.event(r.req_id, "finish",
                                  tokens=len(r.output_ids))
                done.append(r.req_id)
        return done

    def preempt_request(self, rid):
        for q in (self.waiting, self.running):
            for r in list(q):
                if r.req_id == rid:
                    q.remove(r)
                    r.slot = -1
                    self.tracer.event(rid, "preempt",
                                      tokens=len(r.output_ids))
                    return r.prompt_ids, list(r.output_ids)
        raise KeyError(rid)

    def health_payload(self):
        return {"engine_id": self.engine_id,
                "occupancy": len(self.running),
                "slots": self.max_batch_size,
                "waiting": len(self.waiting),
                "free_pages": 100, "total_pages": 100,
                "chunk_queue_depth": 0}


# ---------------------------------------------------------------------------
# tracer units
# ---------------------------------------------------------------------------
def test_tracer_bounds_sampling_and_stub():
    tr = RequestTracer(max_requests=3, max_events_per_request=20)
    for rid in range(5):
        tr.event(rid, "enqueue", ts=1.0)
    # oldest REQUESTS evicted at the cap
    assert tr.request_ids() == [2, 3, 4]
    # per-request cap with a LIFECYCLE RESERVE: bulk spans stop at
    # max_events - 16 total entries (here 4), so after a span flood
    # the finish/preempt instants still land; past the FULL cap even
    # instants drop — counted, never appended
    for i in range(10):
        tr.span(4, "decode_step", 1.0 + i, 1.1 + i)
    assert len(tr.events(4)) == 4             # enqueue + 3 spans
    assert tr.dropped() == 7
    tr.event(4, "finish", ts=99.0)            # lifecycle: still records
    kinds = [e[1] for e in tr.events(4)]
    assert kinds[-1] == "finish"
    for i in range(40):                       # flood instants to the cap
        tr.event(4, "requeue", ts=float(i))
    assert len(tr.events(4)) == 20            # hard cap holds
    # sample_span records every Nth but counts every call
    tr2 = RequestTracer()
    for i in range(10):
        tr2.sample_span(0, "decode_step", float(i), float(i) + 0.5,
                        every=4)
    assert tr2.kind_count(0, "decode_step") == 10
    recorded = [e for e in tr2.events(0) if e[1] == "decode_step"]
    assert len(recorded) == 3                 # samples 0, 4, 8
    assert [e[4]["sample_index"] for e in recorded] == [0, 4, 8]
    # entries carry chrome phases and args
    ph, kind, t0, t1, args = recorded[0]
    assert ph == "X" and t1 - t0 == pytest.approx(0.5)
    # the no-op stub swallows everything and resolve_tracer wires it
    assert resolve_tracer(False) is NULL_TRACER
    assert not NULL_TRACER.enabled
    NULL_TRACER.event(0, "enqueue")
    NULL_TRACER.span(0, "x", 0.0, 1.0)
    assert NULL_TRACER.events(0) == [] and NULL_TRACER.request_ids() == []
    shared = RequestTracer()
    assert resolve_tracer(shared) is shared
    with pytest.raises(TypeError):
        resolve_tracer("yes")


def test_latency_reservoir_bounded_and_deterministic():
    res = LatencyReservoir(capacity=8, seed=3)
    for v in range(100):
        res.add(float(v))
    assert res.count == 100
    d = res.digest()
    assert d["count"] == 100 and d["window"] == 8
    assert 0.0 <= d["p50"] <= 99.0 and d["p50"] <= d["p95"] <= d["p99"]
    # deterministic for a fixed insertion order (seeded Algorithm R)
    res2 = LatencyReservoir(capacity=8, seed=3)
    for v in range(100):
        res2.add(float(v))
    assert res2.digest() == d
    assert LatencyReservoir(capacity=4).digest()["p50"] is None


# ---------------------------------------------------------------------------
# span-chain completeness + SLO arithmetic on the stub router
# ---------------------------------------------------------------------------
def test_span_chain_across_preempt_requeue_and_slo_arithmetic():
    """The tentpole contract on stubs: a preempted-and-requeued victim
    keeps a gap-free chain (pending/on_engine spans tile submit..done,
    every hop re-dispatched), and for each SLO kind the attainment
    outcomes sum to completed admissions."""
    e = _StubEngine(0, slots=1)
    router = ServingRouter([e])
    lo = router.submit(np.arange(4, dtype=np.int64), max_new_tokens=6,
                       priority=0, ttft_target=10.0, tpot_target=10.0)
    router.step()                             # lo runs, has 1 token
    hi = router.submit(np.arange(20, 24, dtype=np.int64),
                       max_new_tokens=1, priority=5,
                       ttft_target=0.0)       # deadline=now: missed
    no_slo = router.submit(np.arange(30, 34, dtype=np.int64),
                           max_new_tokens=1)
    out = router.run_to_completion()
    assert len(out[lo]) == 6                  # preempted, zero loss
    f_lo = router.finished[lo]
    assert f_lo.requeues == 1

    # --- chains: every dispatched request validates gap-free ---------
    for rid in (lo, hi, no_slo):
        ok, why = validate_span_chain(router.tracer.events(rid))
        assert ok, f"rid {rid}: {why}"
    kinds = [ev[1] for ev in router.tracer.events(lo)]
    assert kinds.count("dispatch") == 2       # the requeue hop re-dispatched
    assert kinds.count("requeue") == 1
    assert kinds.count("on_engine") == 2
    req_ev = next(ev for ev in router.tracer.events(lo)
                  if ev[1] == "requeue")
    assert req_ev[4]["reason"] == "preempt" and req_ev[4]["engine"] == 0

    # --- the validator actually rejects holes ------------------------
    broken = [ev for ev in router.tracer.events(lo)
              if ev[1] != "on_engine"]
    ok, why = validate_span_chain(broken)
    assert not ok and "on_engine" in why
    ok, why = validate_span_chain([])
    assert not ok

    # --- SLO arithmetic ----------------------------------------------
    snap = router.slo_snapshot()
    for kind in ("ttft", "tpot"):
        total = sum(snap[kind][o]
                    for o in ("attained", "missed", "no_target"))
        assert total == 3                     # = completed admissions
    assert snap["ttft"]["missed"] >= 1        # the 0.0-deadline request
    assert snap["ttft"]["attained"] >= 1      # the 10s-target victim
    assert snap["tpot"]["no_target"] == 2     # hi (1 token) + no_slo
    assert router.finished[hi].summary["slo"]["ttft"] == "missed"
    assert router.finished[lo].summary["slo"]["ttft"] == "attained"
    # digests live in the health payload
    hp = router.health_payload()
    assert hp["slo"]["ttft"]["count"] == 3
    assert hp["slo"]["ttft"]["p50"] is not None


def test_summary_on_finished_records_and_pop_record():
    """Satellite: streaming drivers read ttft/tpot/requeues/engines off
    the finished record; pop_result keeps its tokens-only contract."""
    e = _StubEngine(0, slots=2)
    router = ServingRouter([e])
    a = router.submit(np.arange(4, dtype=np.int64), max_new_tokens=3)
    router.run_to_completion()
    rr = router.finished[a]
    s = rr.summary
    assert s["tokens"] == 3 and s["requeues"] == 0
    assert s["engines_visited"] == [0]
    assert s["ttft"] is not None and s["ttft"] >= 0
    assert s["mean_tpot"] is not None and s["mean_tpot"] >= 0
    assert s["slo"] == {"ttft": "no_target", "tpot": "no_target"}
    # pop_record consumes the full record, pop_result just the tokens
    rec = router.pop_record(a)
    assert rec is rr and a not in router.finished
    b = router.submit(np.arange(4, dtype=np.int64), max_new_tokens=2)
    router.run_to_completion()
    assert router.pop_result(b) == [7, 7]
    assert b not in router.finished


def test_finished_eviction_keeps_summaries_bounded():
    """Satellite regression: the bounded-`finished` eviction still
    holds with summaries attached — old records (and their summaries)
    leave, recent ones keep theirs."""
    e = _StubEngine(0, slots=2)
    router = ServingRouter([e], max_finished=3)
    rids = [router.submit(np.arange(4, dtype=np.int64),
                          max_new_tokens=1) for _ in range(7)]
    router.run_to_completion()
    assert len(router.finished) == 3
    assert list(router.finished) == rids[-3:]
    assert all(router.finished[r].summary is not None
               for r in rids[-3:])


def test_tracer_off_router_and_engine_still_serve():
    """tracer=False drops to the no-op stub everywhere: identical
    results, zero recorded events (the overhead bench's control arm)."""
    e = _StubEngine(0, slots=1)
    router = ServingRouter([e], tracer=False)
    assert router.tracer is NULL_TRACER
    a = router.submit(np.arange(4, dtype=np.int64), max_new_tokens=2)
    out = router.run_to_completion()
    assert out[a] == [7, 7]
    assert router.tracer.events(a) == []
    # SLO accounting is independent of the tracer
    snap = router.slo_snapshot()
    assert sum(snap["ttft"].get(o, 0)
               for o in ("attained", "missed", "no_target")) == 1


def test_fleet_trace_merges_groups_and_flow_links(tmp_path):
    """fleet_trace writes ONE valid chrome JSON: router + one track
    group per engine, request lanes renamed to fleet rids, and a flow
    s/f pair chaining a lost-engine requeue across engines."""
    e0, e1 = _StubEngine(0, slots=2), _StubEngine(1, slots=2)
    router = ServingRouter([e0, e1])
    rids = [router.submit(np.arange(i, i + 6, dtype=np.int64),
                          max_new_tokens=4) for i in range(4)]
    router.step()
    # kill whichever engine holds work so its requests hop across
    victim = next(h.engine for h in router.handles.values()
                  if any(k[0] == h.engine_id for k in router._inflight))

    def _dead():
        raise RuntimeError("boom")
    victim.step = _dead
    victim_id = next(h.engine_id for h in router.handles.values()
                     if h.engine is victim)
    router.mark_unhealthy(victim_id)      # drain: requests now PENDING
    # mid-incident trace — drained requests sit in router.pending with
    # closed hops; their engine lanes must already be renamed to rids
    mid = fleet_trace(str(tmp_path / "mid.json"), router)
    assert mid["requests"] == len(rids)
    mid_data = json.load(open(str(tmp_path / "mid.json")))
    drained = [rr.rid for rr in router.pending if rr.hops]
    assert drained
    lanes = {e["args"]["name"] for e in mid_data["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "thread_name"}
    assert "req %d" % drained[0] in lanes
    out = router.run_to_completion()
    assert all(len(out[r]) == 4 for r in rids)
    hopped = [r for r in rids
              if len(set(router.finished[r].engines_visited())) > 1]
    assert hopped                               # >=1 cross-engine hop

    path = str(tmp_path / "fleet.json")
    stats = fleet_trace(path, router)
    assert stats["engine_groups"] == 2
    assert stats["cross_engine_links"] >= 1
    data = json.load(open(path))
    assert data["displayTimeUnit"] == "ms"
    evs = data["traceEvents"]
    groups = {e["args"]["name"] for e in evs
              if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert "router" in groups
    assert {"engine 0", "engine 1"} <= groups
    # flow pair: same id/name, "s" and "f", different pids
    flows = [e for e in evs if e.get("cat") == "flow"]
    assert flows
    by_id = {}
    for f in flows:
        by_id.setdefault(f["id"], []).append(f)
    linked = [fs for fs in by_id.values()
              if {f["ph"] for f in fs} == {"s", "f"}
              and len({f["pid"] for f in fs}) == 2]
    assert linked
    s_ev = next(f for f in linked[0] if f["ph"] == "s")
    f_ev = next(f for f in linked[0] if f["ph"] == "f")
    assert f_ev["ts"] >= s_ev["ts"]             # arrow points forward
    assert f_ev.get("bp") == "e"
    # a hopped request keeps ONE lane id (the fleet rid) on BOTH
    # engine pids: its engine-local ids were renamed
    rid = hopped[0]
    pids_with_lane = {e["pid"] for e in evs
                      if e.get("ph") == "M" and e.get("name") == "thread_name"
                      and e["args"]["name"] == "req %d" % rid}
    assert len(pids_with_lane) >= 3             # router + both engines


# ---------------------------------------------------------------------------
# real-engine e2e (slow lane)
# ---------------------------------------------------------------------------
def _tiny_model(seed=0):
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    paddle.seed(seed)
    m = LlamaForCausalLM(llama_tiny_config())
    m.eval()
    return m


@pytest.mark.slow
def test_kill_drill_trace_completeness_real_engines(tmp_path):
    """E2E on real mixed+prefix engines: kill one mid-run; every
    request's chain validates gap-free across the requeue hop, the
    fleet trace carries >=2 engine groups + a cross-engine flow link,
    and attainment counters sum to admissions."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model = _tiny_model()
    engines = [ContinuousBatchingEngine(
        model, max_batch_size=2, num_blocks=96, block_size=4,
        prefill_chunk_size=8,
        enable_prefix_cache=True, engine_id=100 + i) for i in range(2)]
    router = ServingRouter(engines)
    rng = np.random.RandomState(5)
    rids = [router.submit(rng.randint(1, 300, (10,)).astype(np.int64),
                          max_new_tokens=4,
                          ttft_target=60.0 if i % 2 else None)
            for i in range(5)]
    for _ in range(2):
        router.step()
    victim = router.handles[100].engine

    def _dead():
        raise RuntimeError("injected engine loss")
    victim.step = _dead
    out = router.run_to_completion()
    assert all(len(out[r]) == 4 for r in rids)
    for rid in rids:
        ok, why = validate_span_chain(router.tracer.events(rid))
        assert ok, f"rid {rid}: {why}"
    # the ENGINE tracers saw the per-request detail: a prefill span and
    # a finish for every request that ran there
    for h in router.handles.values():
        etr = h.engine.tracer
        for erid in etr.request_ids():
            kinds = {ev[1] for ev in etr.events(erid)}
            assert "admit" in kinds
    snap = router.slo_snapshot()
    for kind in ("ttft", "tpot"):
        assert sum(snap[kind][o] for o in
                   ("attained", "missed", "no_target")) == len(rids)
    path = str(tmp_path / "fleet_real.json")
    stats = fleet_trace(path, router)
    assert stats["engine_groups"] == 2
    assert stats["cross_engine_links"] >= 1
    data = json.load(open(path))
    assert data["traceEvents"][0].get("ph") != "M"
    # engine lanes carry real phase spans (prefill chunks / decode)
    names = {e["name"] for e in data["traceEvents"]}
    assert "prefill_chunk" in names and "decode_step" in names
    assert "first_token" in names and "finish" in names
