"""Fleet capacity & efficiency plane (round 20): windowed signals,
hysteresis planner, serving-step MFU, /healthz surfacing.

Tier-1 stays in the stub lane (no model, no engine, no compiles —
~2s): SignalWindow math under deterministic timestamps AND concurrent
writers, planner hysteresis/dwell/flap behavior driven directly with
synthetic fleet signals, the stub-pool router wiring (plan surface,
defaults-off parity, /healthz in-process + HTTP with the bare-ok
degradation contract), the shared-peak-FLOPs-table identity, and the
MFU gauge arithmetic against an injected efficiency source.  The
real-engine drill (overload -> scale_up, drain -> scale_down, real
compiled cost_analysis efficiency) is @slow per the 870s budget rule.
"""
import json
import threading
import types
import urllib.request

import numpy as np
import pytest

from paddle_tpu.observability.capacity import (
    CAPACITY_ACTIONS, CapacityConfig, CapacityPlanner,
    EngineCapacityMonitor, FleetCapacityMonitor, SignalWindow,
    resolve_capacity_monitor, saturation_of)


# ---------------------------------------------------------------------------
# SignalWindow
# ---------------------------------------------------------------------------
def test_signal_window_rate_ewma_derivative():
    """Counter rate, gauge derivative and the time-decayed EWMA, on
    explicit timestamps (deterministic — no wall clock in the math)."""
    w = SignalWindow(maxlen=8, halflife_s=1.0)
    assert w.rate() == 0.0 and w.derivative() == 0.0    # empty
    assert w.ewma() is None and w.last() is None
    for i in range(5):                    # counter: +10/s
        w.add(10.0 * i, t=100.0 + i)
    assert w.rate() == pytest.approx(10.0)
    assert w.derivative() == pytest.approx(10.0)
    assert w.span() == pytest.approx(4.0)
    # gauge going DOWN: rate clamps at 0 (counter-reset semantics),
    # derivative stays signed
    d = SignalWindow(maxlen=8, halflife_s=1.0)
    for i in range(5):
        d.add(100.0 - 5.0 * i, t=200.0 + i)
    assert d.rate() == 0.0
    assert d.derivative() == pytest.approx(-5.0)
    # EWMA: one exact half-life step halves the distance to the target
    e = SignalWindow(maxlen=8, halflife_s=1.0)
    e.add(0.0, t=0.0)
    e.add(1.0, t=1.0)                     # dt == halflife -> alpha 0.5
    assert e.ewma() == pytest.approx(0.5)
    # bounded: the ring keeps only maxlen samples and the rate is
    # computed over the RETAINED window
    b = SignalWindow(maxlen=4, halflife_s=1.0)
    for i in range(100):
        b.add(float(i), t=float(i))
    assert len(b) == 4
    assert b.span() == pytest.approx(3.0)
    assert b.rate() == pytest.approx(1.0)
    with pytest.raises(ValueError):
        SignalWindow(maxlen=1)


def test_signal_window_concurrent_writers():
    """N writer threads + a reader thread: every statistic stays
    finite and bounded, nothing raises, and the final window holds
    exactly maxlen samples of the written values."""
    w = SignalWindow(maxlen=64, halflife_s=0.5)
    errors = []

    def write(base):
        try:
            for i in range(500):
                w.add(base + i)
        except Exception as e:                        # noqa: BLE001
            errors.append(e)

    def read():
        try:
            for _ in range(500):
                w.rate(), w.ewma(), w.derivative(), w.mean(), len(w)
        except Exception as e:                        # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=write, args=(1000 * k,))
               for k in range(4)] + [threading.Thread(target=read)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(w) == 64
    assert w.last() is not None and np.isfinite(w.ewma())
    assert np.isfinite(w.rate()) and np.isfinite(w.derivative())


def test_fleet_monitor_map_safe_under_concurrent_insertion():
    """A late engine's monitor is inserted by the step thread while a
    /healthz scrape thread iterates the map (fleet_signals /
    capacity_plan) — the locked snapshot must never raise
    'dictionary changed size during iteration'."""
    mon = FleetCapacityMonitor(CapacityConfig(sample_every=1))
    payload = {"occupancy": 1, "slots": 2, "waiting": 0,
               "free_pages": 50, "total_pages": 100}
    errors = []

    def insert():
        try:
            for i in range(300):
                mon.monitor_for(i).sample(payload)
        except Exception as e:                        # noqa: BLE001
            errors.append(e)

    def scrape():
        try:
            for _ in range(300):
                mon.fleet_signals()
                mon.capacity_plan()
                mon._plan = None        # force a rebuild each pass
        except Exception as e:                        # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=insert),
               threading.Thread(target=scrape)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert mon.fleet_signals()["engines"] == 300


# ---------------------------------------------------------------------------
# planner: hysteresis bands + minimum dwell
# ---------------------------------------------------------------------------
def _fleet(sat, pending=0.0, growth=0.0, spread=0.0, engines=2):
    return {"saturation": sat, "pending": pending,
            "queue_growth_per_s": growth, "saturation_spread": spread,
            "engines": engines}


def test_capacity_planner_hysteresis_dwell_and_flap():
    cfg = CapacityConfig(min_dwell=3)
    p = CapacityPlanner(cfg)
    # saturated: candidate scale_up must DWELL 3 evaluations first
    assert p.evaluate(_fleet(0.95)) == "steady"
    assert p.evaluate(_fleet(0.95)) == "steady"
    assert p.evaluate(_fleet(0.95)) == "scale_up"
    assert p.actions == ["scale_up"]
    # hysteresis: dithering around the ENTRY band (0.84 / 0.86, both
    # above high_clear=0.70) never leaves scale_up — zero flaps
    for i in range(20):
        assert p.evaluate(_fleet(0.84 if i % 2 else 0.86)) == "scale_up"
    assert p.actions == ["scale_up"]
    # clears the high band -> steady (after dwell), then idle ->
    # scale_down (after dwell); the committed sequence never reverses
    for _ in range(3):
        p.evaluate(_fleet(0.5))
    assert p.action == "steady"
    for _ in range(3):
        p.evaluate(_fleet(0.1))
    assert p.action == "scale_down"
    assert p.actions == ["scale_up", "steady", "scale_down"]
    # scale_down defends its band: dither around low_watermark (0.2 /
    # 0.3, both under low_clear=0.40) stays committed
    for i in range(20):
        assert p.evaluate(_fleet(0.2 if i % 2 else 0.3)) == "scale_down"
    assert p.actions == ["scale_up", "steady", "scale_down"]
    # pending work instantly disqualifies scale_down's defense
    for _ in range(3):
        p.evaluate(_fleet(0.3, pending=2.0))
    assert p.action == "steady"


def test_capacity_planner_rebalance_and_blips():
    p = CapacityPlanner(CapacityConfig(min_dwell=2))
    # mid-band fleet with a wide per-engine spread -> rebalance
    for _ in range(2):
        p.evaluate(_fleet(0.5, spread=0.6))
    assert p.action == "rebalance"
    # a 1-evaluation saturation blip (below min_dwell) never commits
    p.evaluate(_fleet(0.95))
    assert p.action == "rebalance"
    p.evaluate(_fleet(0.5, spread=0.6))
    assert p.action == "rebalance"
    assert p.actions == ["rebalance"]
    # growing backlog above high_clear escalates without full
    # watermark saturation
    for _ in range(2):
        p.evaluate(_fleet(0.75, pending=4.0, growth=1.0))
    assert p.action == "scale_up"
    with pytest.raises(ValueError):
        CapacityConfig(high_watermark=0.5, high_clear=0.8)
    with pytest.raises(ValueError):
        CapacityConfig(min_dwell=0)
    with pytest.raises(ValueError):
        CapacityConfig(sample_every=0)


# ---------------------------------------------------------------------------
# stub engine pool: router wiring, defaults-off parity, /healthz
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
    """Minimal engine protocol with controllable load + counters."""
    block_size = 4

    def __init__(self, engine_id, slots=1):
        self.engine_id = engine_id
        self.max_batch_size = slots
        self.waiting = []
        self.running = []
        self.finished = {}
        self.prefix_cache = None
        self.tokens = 0
        self._next = 0

    def add_request(self, prompt_ids, max_new_tokens=16,
                    eos_token_id=None):
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
        done = []
        for r in list(self.running):
            r.output_ids.append(7)
            self.tokens += 1
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
                "free_pages": 100, "total_pages": 100,
                "chunk_queue_depth": 0,
                "counters": {"tokens_generated": self.tokens,
                             "requests_admitted": self._next}}


def _stub_router(n=2, slots=1, capacity=True, **kw):
    from paddle_tpu.inference.router import ServingRouter
    engines = [_StubEngine(i, slots=slots) for i in range(n)]
    return ServingRouter(engines, capacity=capacity, **kw), engines


def test_router_capacity_plan_on_stub_pool():
    """The router samples per step, the plan surfaces everywhere it
    should, and an overloaded stub pool recommends scale_up."""
    cfg = CapacityConfig(min_dwell=2, halflife_s=0.001,
                         sample_every=1)
    router, _engines = _stub_router(n=2, slots=1, capacity=cfg)
    rng = np.random.RandomState(0)
    for _ in range(8):                    # 8 requests onto 2 slots
        router.submit(rng.randint(1, 50, (8,)).astype(np.int64),
                      max_new_tokens=4)
    for _ in range(3):
        router.step()
    plan = router.capacity_plan()
    assert plan["action"] == "scale_up"
    assert plan["fleet"]["saturation"] > 0.8
    assert plan["fleet"]["pending"] > 0
    assert set(plan["engines"]) == {"0", "1"}
    for sig in plan["engines"].values():
        assert sig["samples"] >= 1
        assert sig["tokens_per_s"] >= 0.0
    assert plan["bands"]["min_dwell"] == 2
    # the plan rides health_payload, is JSON-serializable as-is, and
    # the recommendation gauges are one-hot on the committed action
    hp = router.health_payload()
    assert hp["capacity"]["action"] == "scale_up"
    json.dumps(hp["capacity"])
    from paddle_tpu.observability import default_registry
    snap = default_registry().snapshot()
    reco = {s["labels"]["action"]: s["value"]
            for s in snap["router_capacity_recommendation"]["series"]}
    assert reco["scale_up"] == 1.0
    assert sum(reco.values()) == 1.0
    assert set(reco) == set(CAPACITY_ACTIONS)
    router.run_to_completion()


def test_lost_engine_leaves_the_fleet_rollup():
    """An unhealthy engine's frozen (typically saturated) windows must
    not pin the fleet saturation/spread/tokens-rate — the planner
    would otherwise chase a ghost engine forever."""
    cfg = CapacityConfig(min_dwell=1, halflife_s=0.001, sample_every=1)
    router, _engines = _stub_router(n=2, slots=1, capacity=cfg)
    rng = np.random.RandomState(1)
    for _ in range(6):
        router.submit(rng.randint(1, 50, (8,)).astype(np.int64),
                      max_new_tokens=3)
    for _ in range(2):
        router.step()            # both engines sampled under load
    import time as _time
    router.mark_unhealthy(1)     # engine 1's windows freeze here
    router.run_to_completion()
    for _ in range(30):
        router.step()            # idle: the survivor's EWMA decays
        _time.sleep(0.001)       # stub steps are µs — give the
                                 # 1ms-halflife EWMA real wall time
    plan = router.capacity_plan()
    assert plan["engines"]["1"]["healthy"] is False
    assert plan["engines"]["0"]["healthy"] is True
    assert plan["fleet"]["engines"] == 1       # rollup = survivors only
    assert plan["fleet"]["saturation"] < 0.2
    assert plan["fleet"]["saturation_spread"] == 0.0
    # recovery puts the engine (and its resumed history) back in
    router.recover_engine(1)
    router.step()
    assert router.capacity_plan()["engines"]["1"]["healthy"] is True


def test_router_capacity_defaults_off_and_knob():
    """No monitor configured: no capacity key, capacity_plan raises,
    and step() takes the exact r19 path (no monitor object at all)."""
    router, _ = _stub_router(capacity=None)
    router.submit(np.arange(1, 9, dtype=np.int64), max_new_tokens=1)
    router.run_to_completion()
    assert router.capacity is None
    assert "capacity" not in router.health_payload()
    with pytest.raises(ValueError):
        router.capacity_plan()
    # the one knob parser
    assert resolve_capacity_monitor(None) is None
    assert resolve_capacity_monitor(False) is None
    mon = FleetCapacityMonitor()
    assert resolve_capacity_monitor(mon) is mon
    assert isinstance(resolve_capacity_monitor(True),
                      FleetCapacityMonitor)
    with pytest.raises(ValueError):
        resolve_capacity_monitor("yes")


def test_capacity_over_healthz_in_process_and_http():
    """The satellite contract: the capacity dict reaches /healthz on
    both the in-process and HTTP paths, and a raising provider still
    degrades to the bare-ok body on both."""
    from paddle_tpu.observability.exporters import (MetricsServer,
                                                    healthz_payload)
    router, _ = _stub_router(
        capacity=CapacityConfig(min_dwell=1, sample_every=1))
    router.submit(np.arange(1, 9, dtype=np.int64), max_new_tokens=2)
    router.step()
    # in-process
    body = healthz_payload(router.health_payload)
    assert body["status"] == "ok"
    assert body["capacity"]["action"] in CAPACITY_ACTIONS
    def _boom():
        raise RuntimeError("stats broke")
    assert healthz_payload(_boom) == {"status": "ok"}
    # HTTP
    srv = MetricsServer(port=0, health_provider=router.health_payload)
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=10) as r:
            scraped = json.loads(r.read().decode("utf-8"))
        assert scraped["status"] == "ok"
        assert scraped["capacity"]["action"] in CAPACITY_ACTIONS
        assert "fleet" in scraped["capacity"]
    finally:
        srv.stop()
    srv = MetricsServer(port=0, health_provider=_boom)
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=10) as r:
            assert json.loads(r.read().decode("utf-8")) \
                == {"status": "ok"}
    finally:
        srv.stop()
    router.run_to_completion()


# ---------------------------------------------------------------------------
# efficiency: shared peak table + MFU arithmetic
# ---------------------------------------------------------------------------
def test_peak_flops_table_is_the_r09_shared_object():
    """No third drifting copy: the capacity module's peak-FLOPs
    symbols ARE telemetry's (bench.py already imports the same),
    verified by object identity, not equality."""
    from paddle_tpu.observability import capacity, telemetry
    assert capacity.PEAK_FLOPS_BY_KIND is telemetry.PEAK_FLOPS_BY_KIND
    assert capacity.device_peak_flops is telemetry.device_peak_flops


def test_efficiency_mfu_arithmetic_from_injected_source():
    """MFU = windowed tokens/s x flops/token / peak, computed from an
    injected efficiency source (no compile); the remote path reads the
    same block off the payload."""
    stats = {"flops_per_token": 2.0e6, "hbm_bytes_per_token": 5.0e5,
             "source": "cost_analysis"}
    eng = types.SimpleNamespace(
        efficiency_stats=lambda compute=False: stats)
    m = EngineCapacityMonitor(7, engine=eng)
    payload = {"occupancy": 1, "slots": 2, "waiting": 0,
               "free_pages": 50, "total_pages": 100,
               "counters": {"tokens_generated": 0}}
    for i in range(5):                    # 100 tokens/s on the window
        payload = dict(payload)
        payload["counters"] = {"tokens_generated": 100 * i}
        m.sample(payload, t=10.0 + i)
    eff = m.efficiency(peak_flops=1.0e9)
    assert eff["tokens_per_s"] == pytest.approx(100.0)
    assert eff["mfu"] == pytest.approx(100.0 * 2.0e6 / 1.0e9)
    assert eff["hbm_bytes_per_token"] == 5.0e5
    # unknown peak: MFU reports 0, never a made-up number (r09 rule)
    assert m.efficiency(peak_flops=None) is not None
    # remote twin: the stats ride the payload's efficiency block
    r = EngineCapacityMonitor(8, engine=None)
    payload2 = dict(payload)
    payload2["efficiency"] = stats
    r.sample(payload2, t=1.0)
    assert r.efficiency(peak_flops=1.0e9)["flops_per_token"] == 2.0e6
    # saturation folds BOTH axes and caps at 1
    assert saturation_of({"occupancy": 3, "slots": 2, "waiting": 1,
                          "free_pages": 0, "total_pages": 10}) == 1.0
    assert saturation_of({}) == 0.0


# ---------------------------------------------------------------------------
# slow lane: real engines end-to-end
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_capacity_e2e_real_engines(monkeypatch, tmp_path):
    """Real 2-engine pool: overload -> scale_up, drain -> scale_down
    with ZERO flaps across the transition; real compiled-step
    efficiency gauges under PADDLE_TPU_MFU_COST_ANALYSIS=1; tokens
    byte-identical to the unmonitored (r19-default) router."""
    import time as _time

    import paddle_tpu as paddle
    from paddle_tpu.inference.router import ServingRouter
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)

    cfg = llama_tiny_config()
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()

    def build_pool(id_base):
        return [ContinuousBatchingEngine(
            model, max_batch_size=2, num_blocks=64, block_size=4,
            prefill_chunk_size=8,
            enable_prefix_cache=True, engine_id=id_base + i)
            for i in range(2)]

    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab_size, (10,)).astype(np.int64)
               for _ in range(10)]

    ccfg = CapacityConfig(min_dwell=2, halflife_s=0.05,
                          low_watermark=0.25, low_clear=0.40,
                          sample_every=1)
    router = ServingRouter(build_pool(0), capacity=ccfg)
    rids = [router.submit(p, max_new_tokens=8) for p in prompts]
    out = {}
    while router.has_work():
        for rid in router.step():
            out[rid] = router.result(rid)
    assert "scale_up" in router.capacity.planner.actions
    # drain: idle steps until the saturation EWMA decays through the
    # low band (fast halflife keeps this sub-second)
    for _ in range(40):
        router.step()
        _time.sleep(0.01)
        if router.capacity.planner.action == "scale_down":
            break
    acts = router.capacity.planner.actions
    assert acts[-1] == "scale_down"
    # zero flaps: each committed action appears exactly once across
    # the overload -> drain transition
    assert len(acts) == len(set(acts))
    # real compiled-step efficiency (env-gated; conftest sets 0)
    monkeypatch.setenv("PADDLE_TPU_MFU_COST_ANALYSIS", "1")
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "1e12")
    eff = router.capacity.refresh_efficiency(compute=True)
    assert set(eff) == {"0", "1"}
    for block in eff.values():
        assert block["flops_per_token"] > 0
        assert block["hbm_bytes_per_token"] > 0
    plan = router.capacity.evaluate()
    e0 = plan["engines"]["0"]["efficiency"]
    assert e0["flops_per_token"] == eff["0"]["flops_per_token"]
    # the engine payload now carries the block for remote scrapers
    eng0 = router.handles[0].engine
    assert eng0.health_payload()["efficiency"]["flops_per_token"] > 0
    # defaults-off parity: an unmonitored router on a fresh pool
    # produces byte-identical streams for the same prompts
    ref_router = ServingRouter(build_pool(10))
    ref_rids = [ref_router.submit(p, max_new_tokens=8) for p in prompts]
    ref_out = ref_router.run_to_completion()
    assert [out[r] for r in rids] == [ref_out[r] for r in ref_rids]
