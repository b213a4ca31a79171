"""Observability subsystem: metrics registry semantics, Prometheus
text-format escaping, step telemetry (MFU/NaN sentinel), trace merging,
checkpoint failure counter (fault-injected), metric-name lint.

HTTP endpoint lifecycle and the serving-engine metric families compile
real XLA modules / bind sockets — slow lane.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import (MetricsRegistry, MetricError,
                                      StepTelemetry, generate_latest,
                                      json_snapshot, merge_chrome_trace,
                                      SpanLog, default_registry)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------
def test_counter_gauge_basics_and_idempotent_registration():
    r = MetricsRegistry()
    c = r.counter("reqs_total", "requests")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(MetricError):
        c.inc(-1)                      # counters only go up
    g = r.gauge("depth", "queue depth")
    g.set(7)
    g.dec(2)
    assert g.value == 5
    # same schema -> the SAME metric object (call-site re-registration)
    assert r.counter("reqs_total", "requests") is c
    # conflicting schema -> error
    with pytest.raises(MetricError):
        r.gauge("reqs_total")
    with pytest.raises(MetricError):
        r.counter("reqs_total", labels=("method",))
    # naming contract enforced at registration
    with pytest.raises(MetricError):
        r.counter("notATotal", "bad case")
    with pytest.raises(MetricError):
        r.counter("missing_suffix", "counters need _total")
    with pytest.raises(MetricError):
        r.gauge("depth_total", "_total reserved for counters")


def test_label_cardinality_and_schema():
    r = MetricsRegistry()
    c = r.counter("rpc_total", "calls", labels=("method", "code"))
    c.labels(method="get", code="200").inc()
    c.labels(method="get", code="500").inc(2)
    c.labels(code="200", method="get").inc()       # kwarg order free
    assert c.labels(method="get", code="200").value == 2
    assert len(c.children()) == 2
    with pytest.raises(MetricError):
        c.labels(method="get")                     # missing label
    with pytest.raises(MetricError):
        c.labels(method="get", code="200", extra="x")
    with pytest.raises(MetricError):
        c.inc()                # labeled metric needs .labels(...)
    snap = r.snapshot()
    assert {s["labels"]["code"] for s in
            snap["rpc_total"]["series"]} == {"200", "500"}


def test_histogram_fixed_buckets():
    r = MetricsRegistry()
    h = r.histogram("wait_seconds", "wait", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.01, 0.05, 0.5, 2.0):
        h.observe(v)
    # raw per-bucket counts: (-inf,0.01], (0.01,0.1], (0.1,1], (1,inf)
    child = h.children()[0]
    assert child._counts == [2, 1, 1, 1]       # 0.01 lands in le=0.01
    assert child.cumulative() == [2, 3, 4, 5]
    assert h.count == 5
    assert abs(h.sum - 2.565) < 1e-9
    with pytest.raises(MetricError):
        r.histogram("bad_seconds", buckets=(1.0, 0.5))   # not increasing
    with pytest.raises(MetricError):
        r.histogram("worse_seconds", buckets=())


def test_concurrent_increments_are_exact():
    r = MetricsRegistry()
    c = r.counter("spins_total", "concurrent")
    h = r.histogram("spin_seconds", "concurrent", buckets=(0.5,))
    n, per = 8, 5000

    def work():
        for _ in range(per):
            c.inc()
            h.observe(0.25)

    threads = [threading.Thread(target=work) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n * per
    assert h.count == n * per
    assert h.children()[0].cumulative()[-1] == n * per


# ---------------------------------------------------------------------------
# prometheus text format
# ---------------------------------------------------------------------------
def test_prometheus_text_format_and_escaping():
    r = MetricsRegistry()
    c = r.counter("odd_total", 'help with \\ and\nnewline',
                  labels=("tag",))
    c.labels(tag='va"l\\ue\nx').inc()
    h = r.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    text = generate_latest(r).decode()
    # HELP escaping: backslash + newline
    assert r"# HELP odd_total help with \\ and\nnewline" in text
    assert "# TYPE odd_total counter" in text
    # label value escaping: backslash, quote, newline
    assert 'odd_total{tag="va\\"l\\\\ue\\nx"} 1' in text
    # histogram exposition: cumulative buckets + +Inf + sum/count
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert "lat_seconds_sum 5.05" in text
    assert "lat_seconds_count 2" in text
    # snapshot is json-able and mirrors the series
    js = json.dumps(json_snapshot(r))
    assert "odd_total" in js and "lat_seconds" in js


# ---------------------------------------------------------------------------
# step telemetry
# ---------------------------------------------------------------------------
def test_step_telemetry_rates_mfu_and_nan_sentinel():
    r = MetricsRegistry()
    tel = StepTelemetry(registry=r, peak_flops=1e12,
                        check_nan_inf=True, hbm_sample_interval=1000)
    tel.set_flops_per_step(5e9)
    tel.on_step(0.01, loss=2.0, examples=8, tokens=1024)
    assert r.get("train_steps_total").value == 1
    assert r.get("train_step_duration_seconds").count == 1
    assert abs(r.get("train_tokens_per_second").value - 102400) < 1
    # MFU = per-device flops / dt / per-chip peak (cost_analysis
    # reports PER-DEVICE flops — no device_count factor)
    want = 5e9 / 0.01 / 1e12
    assert abs(r.get("train_mfu_ratio").value - want) < 1e-6
    # a warmup (compile) step counts but pollutes no histogram/rate
    n_dur = r.get("train_step_duration_seconds").count
    tel.on_step(30.0, loss=2.0, examples=8, tokens=1024, warmup=True)
    assert r.get("train_steps_total").value == 2
    assert r.get("train_step_duration_seconds").count == n_dur
    assert abs(r.get("train_tokens_per_second").value - 102400) < 1
    assert r.get("train_loss").value == 2.0
    # NaN sentinel: counter bumps AND the step raises
    with pytest.raises(FloatingPointError):
        tel.on_step(0.01, loss=float("nan"))
    assert r.get("train_nonfinite_loss_total").value == 1
    # sentinel off: counted but not fatal
    tel2 = StepTelemetry(registry=r, check_nan_inf=False)
    tel2.on_step(0.01, loss=float("inf"))
    assert r.get("train_nonfinite_loss_total").value == 2


def test_train_step_compiled_stats():
    """The MFU FLOPs source: cost_analysis/memory_analysis off the
    compiled fused step, wired into StepTelemetry via
    attach_train_step (Engine.fit's probe; disabled suite-wide in
    conftest for budget, exercised directly here)."""
    import paddle_tpu.nn as nn
    from paddle_tpu.jit.train_step import TrainStep
    paddle.seed(0)
    net = nn.Linear(8, 4)
    opt = paddle.optimizer.SGD(0.1, parameters=net.parameters())
    step = TrainStep(net, lambda o, y: ((o - y) ** 2).mean(), opt)
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    y = paddle.to_tensor(np.ones((4, 4), np.float32))
    step(x, y)
    stats = step.compiled_stats(x, y)
    assert stats.get("flops", 0) > 0
    assert step.compiled_stats(x, y) is stats          # cached
    r = MetricsRegistry()
    tel = StepTelemetry(registry=r, peak_flops=1e12)
    got = tel.attach_train_step(step, x, y)
    assert got["flops"] == stats["flops"]
    assert r.get("train_step_flops").value == stats["flops"]
    tel.on_step(0.01, loss=0.5, examples=4)
    assert r.get("train_mfu_ratio").value > 0


def test_device_memory_stats_api():
    """Satellite: raw PJRT stats dict with a graceful CPU fallback —
    {} / 0, never a raise (SURVEY §5.5 parity)."""
    from paddle_tpu import device
    stats = device.memory_stats()
    assert isinstance(stats, dict)       # {} on XLA CPU
    assert device.memory_allocated() >= 0
    assert device.max_memory_allocated() >= 0
    # out-of-range device index: 0, not IndexError
    assert device.memory_allocated(10 ** 6) == 0
    assert device.max_memory_allocated(10 ** 6) == 0
    assert device.memory_stats(10 ** 6) == {}


# ---------------------------------------------------------------------------
# trace merging (host-only and with runtime spans)
# ---------------------------------------------------------------------------
def test_merge_chrome_trace_host_only_roundtrip(tmp_path):
    """Satellite: valid chrome trace from host spans alone when no
    device trace dir exists; load_profiler_result round-trips it."""
    from paddle_tpu.profiler import (Profiler, RecordEvent,
                                     make_scheduler,
                                     load_profiler_result)
    p = Profiler(timer_only=True,
                 scheduler=make_scheduler(closed=0, ready=0, record=1,
                                          repeat=1))
    p.start()
    with RecordEvent("unit_of_work"):
        time.sleep(0.001)
    p.stop()
    out = str(tmp_path / "sub" / "trace.json")   # dir auto-created
    p.export(out)
    data = load_profiler_result(out)
    assert data["displayTimeUnit"] == "ms"
    evs = data["traceEvents"]
    assert evs and evs[0]["ph"] == "X"
    assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(evs[0])
    names = {e["name"] for e in evs}
    assert "unit_of_work" in names
    assert "process_name" in names               # metadata present
    # no device trace was captured (timer_only): all events host-pid
    assert all(e["pid"] < 1_000_000 for e in evs)


def test_histogram_quantile_pins_against_numpy():
    """Satellite (round 16): Histogram.quantile — linear interpolation
    over the fixed buckets — tracks numpy within one bucket width on a
    known sample, is monotone in q, and saturates at the top finite
    boundary for +Inf-bucket mass."""
    reg = MetricsRegistry()
    buckets = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0)
    h = reg.histogram("q_seconds", "", buckets=buckets)
    rng = np.random.RandomState(7)
    sample = rng.gamma(2.0, 0.05, size=2000)       # latency-shaped
    for v in sample:
        h.observe(float(v))
    bounds = (0.0,) + buckets
    for q in (0.1, 0.5, 0.9, 0.95, 0.99):
        est = h.quantile(q)
        true = float(np.quantile(sample, q))
        # tolerance: the width of the bucket containing the true value
        i = int(np.searchsorted(buckets, true))
        i = min(i, len(buckets) - 1)
        width = buckets[i] - bounds[i]
        assert abs(est - true) <= width, (q, est, true, width)
    qs = [h.quantile(q) for q in (0.05, 0.25, 0.5, 0.75, 0.95)]
    assert qs == sorted(qs)                        # monotone
    # empty histogram -> NaN; all-overflow mass saturates at the top
    h2 = reg.histogram("q2_seconds", "", buckets=(1.0, 2.0))
    assert h2.quantile(0.5) != h2.quantile(0.5)    # NaN
    for _ in range(5):
        h2.observe(100.0)
    assert h2.quantile(0.99) == 2.0
    # labeled children estimate independently
    hl = reg.histogram("q3_seconds", "", labels=("kind",),
                       buckets=(1.0, 2.0, 4.0))
    for _ in range(10):
        hl.labels(kind="decode").observe(0.5)
        hl.labels(kind="prefill").observe(3.0)
    assert hl.labels(kind="decode").quantile(0.5) <= 1.0
    assert hl.labels(kind="prefill").quantile(0.5) > 2.0


def test_span_log_bound_holds_under_concurrent_writers():
    """Satellite (round 16): the append+evict runs under one lock —
    hammering a small SpanLog from several threads never overshoots
    the bound and never corrupts entries."""
    log = SpanLog(maxlen=64)
    n_threads, per_thread = 8, 500
    errs = []

    def writer(tid):
        try:
            for i in range(per_thread):
                log.record("w%d" % tid, float(i), float(i) + 0.5,
                           idx=i)
                if i % 7 == 0:
                    log.instant("i%d" % tid, ts=float(i))
        except Exception as e:                    # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert len(log) == 64                          # exactly the bound
    evs = log.events()
    assert len(evs) == 64
    # entries are intact tuples (no torn writes)
    for ph, name, cat, start, end, args, ident in evs:
        assert ph in ("X", "i") and isinstance(args, dict)
    log.clear()
    assert len(log) == 0


def test_merge_chrome_trace_deterministic_tie_order(tmp_path):
    """Satellite (round 16): two spans sharing a timestamp serialize in
    (pid, tid, name) order — byte-identical output across runs."""
    from paddle_tpu.profiler import _HostEvent
    t = time.perf_counter()
    host = [_HostEvent("zeta", t, t + 0.1, 5),
            _HostEvent("alpha", t, t + 0.1, 3)]   # same ts, two tids
    log = SpanLog()
    log.record("mid", t, t + 0.05)                # same ts, higher pid
    out1 = merge_chrome_trace(str(tmp_path / "a.json"),
                              host_events=host, runtime_events=log)
    out2 = merge_chrome_trace(str(tmp_path / "b.json"),
                              host_events=list(reversed(host)),
                              runtime_events=log)
    d1, d2 = json.load(open(out1)), json.load(open(out2))
    # identical content regardless of input order
    assert d1["traceEvents"] == d2["traceEvents"]
    spans = [e for e in d1["traceEvents"] if e["ph"] != "M"]
    keys = [(e["ts"], e["pid"], e["tid"], e["name"]) for e in spans]
    assert keys == sorted(keys)
    # metadata still trails, first event is a real span
    assert d1["traceEvents"][0]["ph"] != "M"
    assert d1["traceEvents"][-1]["ph"] == "M"


def test_merge_chrome_trace_extra_groups(tmp_path):
    """extra_groups render as their own pids on the SHARED clock (the
    fleet_trace transport)."""
    t = time.perf_counter()
    log = SpanLog()
    log.record("runtime_span", t, t + 0.01)
    group = [{"name": "req 0", "cat": "request", "ph": "X",
              "tid": 0, "ts": t + 1.0, "dur": 0.5},
             {"name": "thread_name", "ph": "M", "tid": 0,
              "args": {"name": "req 0"}}]
    out = merge_chrome_trace(str(tmp_path / "g.json"),
                             runtime_events=log,
                             extra_groups=[("engine 9", group)])
    data = json.load(open(out))
    names = {e["args"]["name"] for e in data["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert "engine 9" in names
    span = next(e for e in data["traceEvents"] if e["name"] == "req 0")
    rt = next(e for e in data["traceEvents"]
              if e["name"] == "runtime_span")
    # one clock: the request span sits 1s after the runtime span
    assert abs((span["ts"] - rt["ts"]) - 1.0 * 1e6) < 1e3
    assert span["dur"] == pytest.approx(0.5 * 1e6)
    assert span["pid"] != rt["pid"]


def test_merge_chrome_trace_with_runtime_spans(tmp_path):
    from paddle_tpu.profiler import _HostEvent
    log = SpanLog()
    t = time.perf_counter()
    log.record("ckpt_write", t + 2.0, t + 2.01, cat="checkpoint",
               step=7)
    log.instant("comm_timeout:allreduce", ts=t + 3.0, cat="comm")
    # host span 2s BEFORE the ckpt span, same perf_counter clock
    host = [_HostEvent("train_region", t, t + 0.5, 1)]
    out = merge_chrome_trace(str(tmp_path / "merged.json"),
                             host_events=host, runtime_events=log)
    data = json.load(open(out))
    names = {e["name"] for e in data["traceEvents"]}
    assert "ckpt_write" in names and "comm_timeout:allreduce" in names
    span = next(e for e in data["traceEvents"]
                if e["name"] == "ckpt_write")
    assert span["ph"] == "X" and span["args"]["step"] == 7
    inst = next(e for e in data["traceEvents"]
                if e["name"].startswith("comm_timeout"))
    assert inst["ph"] == "i"
    # ONE clock: the ckpt span sits 2s after the host span's start,
    # not renormalized to its own t=0
    host_ev = next(e for e in data["traceEvents"]
                   if e["name"] == "train_region")
    assert abs((span["ts"] - host_ev["ts"]) - 2.0 * 1e6) < 1e3


def test_merge_chrome_trace_lands_a_step_on_its_span(tmp_path):
    """Where the span log's step records pair with the profiler's
    ``engine.step`` spans, the merged file has ONE clock, the trace's:
    each ``serving.step`` record lies on its step's span, and the trace
    keeps its own timestamps."""
    import jax
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    from paddle_tpu.observability import load_device_trace_events, span_log
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny_config(
        num_hidden_layers=1, hidden_size=32, num_attention_heads=2,
        num_key_value_heads=1, vocab_size=64, intermediate_size=64))
    model.eval()
    eng = ContinuousBatchingEngine(model, max_batch_size=2, num_blocks=16,
                                   block_size=4, prefill_chunk_size=4)
    eng.add_request(np.arange(1, 8, dtype=np.int64), 5)
    eng.step()                                   # compiles outside the trace
    trace_dir = str(tmp_path / "trace")
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        while eng.has_work():
            eng.step()
    finally:
        jax.profiler.stop_trace()
    out = merge_chrome_trace(str(tmp_path / "merged.json"),
                             runtime_events=span_log,
                             device_trace_dir=trace_dir)
    events = json.load(open(out))["traceEvents"]
    spans = {(int(e["args"]["engine"]), int(e["args"]["step_num"])):
             e["ts"] for e in events if e.get("name") == "engine.step"}
    landed = [(e["ts"], spans[(e["args"]["engine"], e["args"]["step"])])
              for e in events if e.get("name") == "serving.step"
              and (e["args"]["engine"], e["args"]["step"]) in spans]
    assert len(landed) == len(spans) >= 3
    assert all(abs(rec - span) < 200 for rec, span in landed)
    # the profiler's events keep the trace's own timestamps
    raw = sorted(e["ts"] for e in load_device_trace_events(trace_dir)
                 if e.get("name") == "engine.step")
    assert sorted(spans.values()) == pytest.approx(raw)


# ---------------------------------------------------------------------------
# checkpoint failure counter under fault injection
# ---------------------------------------------------------------------------
def test_ckpt_failure_counter_increments(tmp_path):
    from paddle_tpu.distributed.checkpoint import CheckpointManager
    from paddle_tpu.testing import faults
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    failures = default_registry().get("checkpoint_failures_total")
    commits = default_registry().get("checkpoint_commits_total")
    f0, c0 = failures.value, commits.value
    values = {"w": np.arange(8, dtype=np.float32)}
    faults.configure("ioerror:ckpt.write")
    try:
        with pytest.raises(OSError):
            mgr.save(1, values, {"global_step": 1}, sync=True)
    finally:
        faults.configure(None)
    assert failures.value == f0 + 1
    assert commits.value == c0                  # nothing committed
    # healthy save afterwards: commit counter moves, failures don't
    mgr.save(2, values, {"global_step": 2}, sync=True)
    assert commits.value == c0 + 1
    assert failures.value == f0 + 1
    assert mgr.latest_valid()[0] == 2


# ---------------------------------------------------------------------------
# CI lint (satellite: runs in the verify flow via this test)
# ---------------------------------------------------------------------------
def test_metric_name_lint():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "check_metric_names.py")],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "0 violations" in proc.stdout


def test_metric_label_cardinality_lint_rejects_bad_sites():
    """Round-16 satellite: the label-cardinality rule — undeclared
    label names, out-of-domain literal values, and per-request-id
    value expressions are all violations; declared-dynamic labels
    (engine ids) pass."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from check_metric_names import lint_label_sites, _split_kwargs
    finally:
        sys.path.pop(0)
    ok_sites = [
        ("a.py", 1, "outcome", '"completed"'),
        ("a.py", 2, "outcome", '"truncated" if x else "completed"'),
        ("a.py", 3, "engine", "str(h.engine_id)"),
        ("a.py", 4, "reason", "reason"),       # declared, no literal
    ]
    assert lint_label_sites(ok_sites) == []
    bad = lint_label_sites([
        ("b.py", 1, "request", "str(rr.rid)"),        # undeclared name
        ("b.py", 2, "outcome", '"exploded"'),         # out of domain
        ("b.py", 3, "engine", "str(req.req_id)"),     # per-request id
        ("b.py", 4, "kind", "str(uuid.uuid4())"),     # uuid value
    ])
    assert len(bad) == 4
    assert "not declared" in bad[0]
    assert "outside its declared domain" in bad[1]
    assert "per-request identifier" in bad[2]
    # the kwarg splitter handles nesting + quoted commas
    assert _split_kwargs('a="x,y", b=str(f(1, 2)), c=3') == [
        ("a", '"x,y"'), ("b", "str(f(1, 2))"), ("c", "3")]


# ---------------------------------------------------------------------------
# slow lane: HTTP endpoint lifecycle + serving metric families
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_http_endpoint_lifecycle():
    import urllib.error
    import urllib.request
    from paddle_tpu.observability import MetricsServer
    r = MetricsRegistry()
    r.counter("pings_total", "demo").inc(3)
    srv = MetricsServer(port=0, addr="127.0.0.1", registry=r).start()
    try:
        port = srv.port
        assert port and srv.running
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read()
        assert b"pings_total 3" in body
        hz = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10)
        assert hz.status == 200 and b"ok" in hz.read()
        nf = urllib.request.urlopen  # 404 path
        with pytest.raises(urllib.error.HTTPError):
            nf(f"http://127.0.0.1:{port}/nope", timeout=10)
    finally:
        srv.stop()
    assert not srv.running
    # clean shutdown: the port is actually released
    with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=2)
    # env-var port resolution
    os.environ["PADDLE_TPU_METRICS_PORT"] = "0"
    try:
        srv2 = MetricsServer(addr="127.0.0.1", registry=r).start()
        assert srv2.port
        srv2.stop()
    finally:
        del os.environ["PADDLE_TPU_METRICS_PORT"]


@pytest.mark.slow
def test_serving_engine_metric_families():
    """The continuous-batching engine populates every serving family;
    the truncated-victim counter moves under lazy_alloc pool
    exhaustion."""
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    paddle.seed(0)
    cfg = llama_tiny_config(num_hidden_layers=2, hidden_size=64,
                            num_attention_heads=4,
                            num_key_value_heads=2, vocab_size=128,
                            intermediate_size=128)
    model = LlamaForCausalLM(cfg)
    model.eval()
    r = default_registry()
    eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                   num_blocks=16, block_size=4)
    prefill0 = r.get("serving_prefill_duration_seconds").count
    tokens0 = r.get("serving_tokens_total").value
    eng.add_request(np.array([3, 14, 15], np.int64), max_new_tokens=4)
    assert r.get("serving_queue_depth").value == 1
    eng.add_request(np.array([1, 2], np.int64), max_new_tokens=4)
    eng.step()
    assert r.get("serving_slot_occupancy_ratio").value == 1.0
    assert r.get("serving_kv_page_utilization_ratio").value > 0
    eng.run_to_completion()
    # both prompts prefilled in ONE step, the first of its budget: the
    # compile warmup stays out of the latency histogram
    assert r.get("serving_prefill_duration_seconds").count == prefill0
    assert r.get("serving_decode_step_duration_seconds").count > 0
    assert r.get("serving_ttft_seconds").count >= 2
    assert r.get("serving_tpot_seconds").count >= 2
    assert r.get("serving_tokens_total").value == tokens0 + 8
    assert r.get("serving_queue_depth").value == 0
    # five tokens again, alone: the same budget, warm, IS observed
    eng.add_request(np.array([9, 8, 7, 6, 5], np.int64), max_new_tokens=2)
    eng.run_to_completion()
    assert r.get("serving_prefill_duration_seconds").count \
        == prefill0 + 1

    # pool-dry victim: lazy_alloc with a pool too small for both tails
    trunc0 = r.get("serving_truncated_victims_total").value
    done0 = r.get("serving_requests_total").labels(
        outcome="truncated").value if any(
        c.labels.get("outcome") == "truncated"
        for c in r.get("serving_requests_total").children()) else 0
    eng2 = ContinuousBatchingEngine(model, max_batch_size=2,
                                    num_blocks=4, block_size=4,
                                    max_seq_len=32, lazy_alloc=True)
    eng2.add_request(np.arange(1, 8, dtype=np.int64),
                     max_new_tokens=24)
    eng2.add_request(np.arange(1, 8, dtype=np.int64),
                     max_new_tokens=24)
    eng2.run_to_completion()
    assert r.get("serving_truncated_victims_total").value > trunc0
    assert r.get("serving_requests_total").labels(
        outcome="truncated").value > done0
