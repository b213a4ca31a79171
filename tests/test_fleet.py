"""Multi-process serving fleet (round 23): wire protocol, retry
policy, remote engine client/server, and the router's engine-lost
drain — plus the slow-lane real-subprocess drills (byte parity,
cross-socket migration, kill -9, fault-injected hang).

Tier-1 here is sockets-and-stubs only (no model builds, no
subprocesses): framing round-trips over a socketpair, KVPageBuffer
byte parity across the wire, retry/backoff arithmetic on a stub rng,
dedup under injected drops, and the engine_lost requeue driven from
the router's own record through a stub client.
"""
import socket
import time

import numpy as np
import pytest

from paddle_tpu.inference.fleet import (
    EngineRPCError, EngineServer, ProtocolError, RemoteEngineClient,
    RetryPolicy, buffer_from_wire, buffer_to_wire, recv_frame,
    send_frame)
from paddle_tpu.inference.router import EngineHandle, ServingRouter
from paddle_tpu.ops.paged_attention import KVPageBuffer
from paddle_tpu.testing import faults


@pytest.fixture(autouse=True)
def _reset_faults():
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# stub engine (the test_serving_router contract, server-side here)
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
        self.state = "waiting"


class _StubEngine:
    """Deterministic fake engine: each step admits waiting requests to
    slots and appends ``base + len(output)`` so streams are reproducible
    wherever the request runs."""
    block_size = 4

    def __init__(self, engine_id=0, slots=2, token_base=0):
        self.engine_id = engine_id
        self.role = "mixed"
        self.token_base = token_base
        self.waiting = []
        self.slots = [None] * slots
        self.finished = {}
        self.prefix_cache = None
        self._next = engine_id * 1000
        self.steps = 0

    def add_request(self, prompt_ids, max_new_tokens=16,
                    eos_token_id=None, **kw):
        self._next += 1
        r = _StubReq(self._next, prompt_ids, max_new_tokens)
        self.waiting.append(r)
        return r.req_id

    def has_work(self):
        return bool(self.waiting) or any(s is not None
                                         for s in self.slots)

    def step(self):
        self.steps += 1
        done = []
        for r in list(self.waiting):
            if None not in self.slots:
                break
            i = self.slots.index(None)
            self.slots[i] = r
            r.slot, r.state = i, "running"
            self.waiting.remove(r)
        for r in [s for s in self.slots if s is not None]:
            r.output_ids.append(self.token_base + len(r.output_ids))
            if len(r.output_ids) >= r.max_new_tokens:
                self.slots[r.slot] = None
                r.state = "done"
                self.finished[r.req_id] = r
                done.append(r.req_id)
        return done

    def preempt_request(self, req_id):
        for r in list(self.waiting) + [s for s in self.slots
                                       if s is not None]:
            if r.req_id == req_id:
                if r.slot >= 0:
                    self.slots[r.slot] = None
                else:
                    self.waiting.remove(r)
                return r.prompt_ids, list(r.output_ids)
        raise KeyError(req_id)

    def health_payload(self):
        return {"engine_id": self.engine_id,
                "occupancy": sum(s is not None for s in self.slots),
                "slots": len(self.slots),
                "waiting": len(self.waiting),
                "free_pages": 8, "total_pages": 8}


@pytest.fixture
def served_stub():
    """One EngineServer over a stub engine + a tight-deadline client."""
    eng = _StubEngine(engine_id=7)
    srv = EngineServer(eng, idle_poll_s=0.05).start()
    cli = RemoteEngineClient(
        srv.address,
        retry=RetryPolicy(max_attempts=3, base_delay=0.01,
                          max_delay=0.05),
        timeouts={"hello": 2.0, "add_request": 1.0, "step": 1.0,
                  "preempt_request": 1.0, "health_payload": 0.5})
    yield eng, srv, cli
    cli.close()
    srv.stop()


# ---------------------------------------------------------------------------
# framing
# ---------------------------------------------------------------------------
def test_frame_roundtrip_over_socketpair():
    a, b = socket.socketpair()
    try:
        msg = {"id": 3, "method": "step", "params": {"x": [1, 2, 3]}}
        blobs = [b"\x00\x01\x02" * 100, b""]
        send_frame(a, msg, blobs, deadline=time.monotonic() + 2)
        got, gblobs = recv_frame(b, deadline=time.monotonic() + 2)
        assert got == msg
        assert gblobs == blobs
    finally:
        a.close()
        b.close()


def test_frame_bad_magic_raises_protocol_error():
    a, b = socket.socketpair()
    try:
        a.sendall(b"JUNK" + b"\x00" * 8)
        with pytest.raises(ProtocolError):
            recv_frame(b, deadline=time.monotonic() + 1)
    finally:
        a.close()
        b.close()


def test_kv_buffer_wire_byte_parity():
    rng = np.random.RandomState(5)
    # int8 pool WITH per-page scales, the gnarlier of the two planes
    codes = rng.randint(-127, 127, (2 * 2, 3, 4, 2, 8)).astype(np.int8)
    scales = rng.rand(4, 3, 2).astype(np.float32)
    buf = KVPageBuffer(codes=codes, scales=scales, n_pages=3,
                       n_tokens=10, block_size=4, num_kv_heads=2,
                       head_dim=8, num_layers=2, kv_dtype="int8")
    header, blobs = buffer_to_wire(buf)
    a, b = socket.socketpair()
    try:
        send_frame(a, {"id": 1, "buffer": header}, blobs,
                   deadline=time.monotonic() + 2)
        msg, gblobs = recv_frame(b, deadline=time.monotonic() + 2)
    finally:
        a.close()
        b.close()
    out = buffer_from_wire(msg["buffer"], gblobs)
    assert out.codes.tobytes() == codes.tobytes()
    assert out.scales.tobytes() == scales.tobytes()
    assert out.geometry() == buf.geometry()
    assert (out.n_pages, out.n_tokens) == (3, 10)
    # fp32 plane without scales
    f32 = rng.rand(2 * 1, 2, 4, 2, 8).astype(np.float32)
    buf2 = KVPageBuffer(codes=f32, scales=None, n_pages=2, n_tokens=8,
                        block_size=4, num_kv_heads=2, head_dim=8,
                        num_layers=1, kv_dtype="float32")
    h2, b2 = buffer_to_wire(buf2)
    out2 = buffer_from_wire(h2, b2)
    assert out2.codes.tobytes() == f32.tobytes()
    assert out2.scales is None


def test_kv_buffer_wire_validates_before_side_effects():
    header, blobs = buffer_to_wire(KVPageBuffer(
        codes=np.zeros((2, 1, 4, 2, 8), np.float32), scales=None,
        n_pages=1, n_tokens=4, block_size=4, num_kv_heads=2,
        head_dim=8, num_layers=1, kv_dtype="float32"))
    with pytest.raises(ValueError):
        buffer_from_wire(header, [blobs[0][:-4]])    # torn codes blob
    with pytest.raises(ValueError):
        buffer_from_wire({"num_layers": 1}, blobs)   # malformed header
    assert buffer_from_wire(None, []) is None


# ---------------------------------------------------------------------------
# retry policy (stub clock/rng — pure arithmetic)
# ---------------------------------------------------------------------------
def test_retry_policy_backoff_arithmetic():
    class _Rng:
        def random(self):
            return 0.5
    slept = []
    p = RetryPolicy(max_attempts=5, base_delay=0.1, max_delay=0.4,
                    jitter=0.5, rng=_Rng(), sleep=slept.append)
    # base * 2^(k-1) capped at max_delay, times (1 + 0.5*0.5)
    assert [round(p.delay(k), 6) for k in (1, 2, 3, 4)] == \
        [0.125, 0.25, 0.5, 0.5]
    calls = []

    def fn():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert p.run(fn) == "ok"
    assert len(calls) == 3
    assert [round(s, 6) for s in slept] == [0.125, 0.25]

    # retries exhausted: the final failure propagates
    slept.clear()
    p2 = RetryPolicy(max_attempts=2, base_delay=0.1, jitter=0.0,
                     rng=_Rng(), sleep=slept.append)
    with pytest.raises(OSError):
        p2.run(lambda: (_ for _ in ()).throw(OSError("down")))
    assert len(slept) == 1   # one backoff between the two attempts


def test_retry_policy_jitter_bounds():
    p = RetryPolicy(max_attempts=3, base_delay=0.2, max_delay=5.0,
                    jitter=0.5)
    for k in (1, 2, 3):
        base = 0.2 * 2 ** (k - 1)
        for _ in range(50):
            d = p.delay(k)
            assert base <= d <= base * 1.5


# ---------------------------------------------------------------------------
# client <-> server over a real socket (in-process, stub engine)
# ---------------------------------------------------------------------------
def test_rpc_roundtrip_full_engine_surface(served_stub):
    eng, srv, cli = served_stub
    assert cli.engine_id == 7
    assert cli.role == "mixed"
    assert cli.block_size == 4
    assert cli.prefix_cache is None
    erid = cli.add_request(np.arange(5), max_new_tokens=3)
    assert [v.req_id for v in cli.waiting] == [erid]
    assert cli.has_work()
    done = []
    for _ in range(5):
        if not cli.has_work():
            break
        done += cli.step()
    assert done == [erid]
    rec = cli.finished.pop(erid)
    assert rec.output_ids == [0, 1, 2]
    assert rec.t_first_token > 0          # stamped on the CLIENT clock
    assert not cli.has_work()
    # preempt round-trip + KeyError for an unknown id (the in-process
    # error contract crosses the wire as types, not strings)
    e2 = cli.add_request(np.arange(3), max_new_tokens=10)
    cli.step()
    prompt, gen = cli.preempt_request(e2)
    assert prompt.tolist() == [0, 1, 2] and gen == [0]
    with pytest.raises(KeyError):
        cli.preempt_request(999999)
    assert cli.health_payload()["engine_id"] == 7


def test_step_retry_is_dedup_safe_under_drop(served_stub):
    """A dropped request frame -> deadline -> resend; the server's
    (token, id) dedup executes the step ONCE and replays the cached
    response — retried steps never double-advance the engine."""
    eng, srv, cli = served_stub
    erid = cli.add_request(np.arange(4), max_new_tokens=2)
    # hit 1 = the client's step request (passes), hit 2 = the SERVER's
    # response send (dropped): the engine executed, the reply vanished,
    # the client deadline fires and the resend gets the CACHED response
    faults.configure("drop:rpc.send:after=2:times=1")
    done = cli.step()
    faults.configure(None)
    assert eng.steps == 1                  # exactly one engine step
    assert done == []                      # request admitted, not done
    done = cli.step()
    assert done == [erid] and eng.steps == 2
    assert cli.finished[erid].output_ids == [0, 1]
    from paddle_tpu.observability.metrics import default_registry
    m = default_registry().get("router_rpc_retries_total")
    assert m is not None
    retried = {ch.labels["method"]: ch.value for ch in m.children()}
    assert retried.get("step", 0) >= 1


def test_econnreset_retries_then_succeeds(served_stub):
    eng, srv, cli = served_stub
    cli.add_request(np.arange(4), max_new_tokens=1)
    faults.configure("econnreset:rpc.recv:after=1:times=1")
    done = cli.step()
    faults.configure(None)
    assert len(done) == 1 and eng.steps == 1


def test_retries_exhausted_raises_engine_rpc_error(served_stub):
    eng, srv, cli = served_stub
    cli.add_request(np.arange(2), max_new_tokens=1)
    faults.configure("drop:rpc.send")      # every send vanishes
    t0 = time.monotonic()
    with pytest.raises(EngineRPCError) as ei:
        cli.step()
    faults.configure(None)
    assert ei.value.method == "step"
    assert ei.value.attempts == 3
    # bounded: attempts x deadline + backoff, nowhere near a hang
    assert time.monotonic() - t0 < 10.0


def test_server_accept_fault_then_recovery(served_stub):
    eng, srv, cli = served_stub
    cli.close()                            # force a fresh connection
    faults.configure("econnreset:rpc.accept:after=1:times=1")
    # server kills the first accepted conn; client reconnects + retries
    assert cli.health_payload()["engine_id"] == 7
    faults.configure(None)


# ---------------------------------------------------------------------------
# router integration: engine_lost drains from the ROUTER's record
# ---------------------------------------------------------------------------
class _DeadClient:
    """Stub RemoteEngineClient whose process just died: every RPC
    raises EngineRPCError, but the router-side record (views + finished)
    survives — exactly what _lose_engine drains from."""
    block_size = 4

    def __init__(self, engine_id, views):
        self.engine_id = engine_id
        self.role = "mixed"
        self.prefix_cache = None
        self.finished = {}
        self._views = {v.req_id: v for v in views}

    @property
    def waiting(self):
        return [v for v in self._views.values() if v.slot < 0]

    @property
    def slots(self):
        return [v for v in self._views.values() if v.slot >= 0]

    def has_work(self):
        return bool(self._views)

    def add_request(self, *a, **kw):
        raise EngineRPCError("rpc failed after 3 attempts",
                             method="add_request", attempts=3)

    def step(self):
        raise EngineRPCError("rpc failed after 3 attempts",
                             method="step", attempts=3)

    def preempt_request(self, req_id):
        raise EngineRPCError("rpc failed after 3 attempts",
                             method="preempt_request", attempts=3)

    def health_payload(self):
        raise EngineRPCError("rpc failed after 3 attempts",
                             method="health_payload", attempts=3)


def test_engine_lost_requeue_from_router_record_with_stub_client():
    from paddle_tpu.inference.fleet import RemoteRequestView
    survivor = _StubEngine(engine_id=1, slots=4, token_base=50)
    # the dead engine had generated 2 tokens for its one running view
    view = RemoteRequestView(req_id=2001, output_ids=[50, 51], slot=0,
                             state="running", t_first_token=time.
                             perf_counter())
    dead = _DeadClient(engine_id=2, views=[view])
    router = ServingRouter([survivor, dead],
                           probe_failure_threshold=1)
    rid = router.submit(np.arange(6), max_new_tokens=4)
    # force the pending request onto the dead client's books the way a
    # dispatch would have (we can't dispatch through it — RPCs raise)
    rr = router.pending[0]
    rr.state = "dispatched"
    rr.engine_id = 2
    rr.engine_req_id = 2001
    rr.engine_req = view
    rr.hops.append([2, 2001, time.perf_counter(), None])
    router.pending.clear()
    router._inflight[(2, 2001)] = rr
    out = router.run_to_completion()
    # zero drops: the tokens the dead engine generated (router record)
    # survive, the remainder regenerates on the survivor
    assert out[rid][:2] == [50, 51]
    assert len(out[rid]) == 4
    assert not router.handles[2].healthy
    from paddle_tpu.observability.metrics import default_registry
    m = default_registry().get("router_requeues_total")
    req = {ch.labels["reason"]: ch.value for ch in m.children()}
    assert req.get("engine_lost", 0) >= 1


def test_router_drives_remote_engines_and_survives_server_death():
    """Two stub engines behind REAL sockets; one server dies mid-run
    (no shutdown RPC — sockets just go dark).  Every request completes,
    with >=1 engine_lost requeue and the survivor finishing the work."""
    engines = [_StubEngine(engine_id=i, slots=2, token_base=100 * i)
               for i in (1, 2)]
    servers = [EngineServer(e, idle_poll_s=0.05).start()
               for e in engines]
    clients = [RemoteEngineClient(
        s.address,
        retry=RetryPolicy(max_attempts=2, base_delay=0.01,
                          max_delay=0.02),
        timeouts={"hello": 2.0, "add_request": 0.5, "step": 0.5,
                  "preempt_request": 0.5, "extract_request": 0.5,
                  "health_payload": 0.3}) for s in servers]
    try:
        router = ServingRouter(clients, probe_failure_threshold=2)
        rids = [router.submit(np.arange(4) + i, max_new_tokens=4)
                for i in range(4)]
        for _ in range(2):
            router.step()
        servers[0].stop()                  # dark, mid-flight
        out = router.run_to_completion()
        assert sorted(out) == sorted(rids)
        assert all(len(v) == 4 for v in out.values())
        healthy = [h for h in router.handles.values() if h.healthy]
        assert len(healthy) == 1
    finally:
        for c in clients:
            c.close()
        for s in servers:
            s.stop()


def test_engine_handle_healthz_scrape_retries():
    """The /healthz scrape satellite: one flaky read retries inside the
    probe via the shared RetryPolicy instead of burning a probe-failure
    count."""
    calls = []

    class _FlakyEngine:
        def health_payload(self):
            calls.append(1)
            if len(calls) < 2:
                raise OSError("scrape blip")
            return {"occupancy": 0, "slots": 2, "waiting": 0}

    h = EngineHandle(_FlakyEngine(), engine_id=9,
                     retry=RetryPolicy(max_attempts=3, base_delay=0.0,
                                       jitter=0.0))
    # in-process payload() doesn't retry (no wire) — probe() fails once
    assert h.probe() is False
    calls.clear()

    # the URL path retries through RetryPolicy.run: simulate with a
    # handle whose scrape fn we drive directly
    attempts = []

    def flaky_scrape():
        attempts.append(1)
        if len(attempts) < 3:
            raise OSError("timeout")
        return {"ok": 1}

    assert h.retry.run(flaky_scrape) == {"ok": 1}
    assert len(attempts) == 3


# ---------------------------------------------------------------------------
# slow lane: real subprocesses, real engines
# ---------------------------------------------------------------------------
def _load_engine_server_module():
    import importlib.util
    from pathlib import Path
    path = Path(__file__).resolve().parents[1] / "tools" / \
        "engine_server.py"
    spec = importlib.util.spec_from_file_location("engine_server", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_FLEET_CFG = {
    "platform": "cpu", "seed": 0, "slots": 2, "num_blocks": 96,
    "block_size": 4, "chunk": None,
    "enable_prefix_cache": False, "warm": {"prompt_len": 12,
                                           "budget": 4},
}


def _spawn_pool(n, extra_env=None, cfg_overrides=None):
    from paddle_tpu.inference.fleet import EngineProcess
    procs = []
    for i in range(n):
        cfg = dict(_FLEET_CFG, engine_id=10 + i)
        if cfg_overrides:
            cfg.update(cfg_overrides)
        procs.append(EngineProcess(
            cfg, env={"JAX_PLATFORMS": "cpu", **(extra_env or {})},
            startup_timeout=600.0))
    addrs = [p.spawn() for p in procs]
    return procs, addrs


def _fleet_clients(addrs, step_timeout=240.0):
    return [RemoteEngineClient(
        a, retry=RetryPolicy(max_attempts=2, base_delay=0.05,
                             max_delay=0.5),
        timeouts={"step": step_timeout, "add_request": 60.0,
                  "hello": 60.0, "extract_request": 120.0,
                  "inject_request": 240.0, "preempt_request": 60.0,
                  "health_payload": 10.0}) for a in addrs]


@pytest.fixture(scope="module")
def fleet_pool():
    """Two real engine-server subprocesses (tiny llama, warmed) — the
    LAST test using this fixture kills process 0 on purpose."""
    procs, addrs = _spawn_pool(2)
    yield procs, addrs
    for p in procs:
        p.kill()


@pytest.fixture(scope="module")
def eager_oracle():
    """The r15 parity oracle: eager greedy generate on the SAME seeded
    tiny model the subprocess engines built."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from tools.bench_common import build_bench_model, eager_reference
    cfg, model = build_bench_model(on_tpu=False)
    return cfg, model, eager_reference


def _fleet_prompts(vocab, n=4, rng_seed=3):
    rng = np.random.RandomState(rng_seed)
    return [rng.randint(1, vocab - 60, (6 + i,)).astype(np.int64)
            for i in range(n)]


@pytest.mark.slow
def test_multiprocess_pool_byte_parity(fleet_pool, eager_oracle):
    procs, addrs = fleet_pool
    cfg, model, eager_reference = eager_oracle
    clients = _fleet_clients(addrs)
    try:
        router = ServingRouter(clients)
        prompts = _fleet_prompts(cfg.vocab_size, n=4)
        budget = 5
        rids = [router.submit(p, max_new_tokens=budget)
                for p in prompts]
        out = router.run_to_completion()
        assert sorted(out) == sorted(rids)
        used = set()
        for r in rids:
            used.update(router.finished[r].engines_visited())
        assert len(used) == 2, "expected both processes to serve"
        for rid, prompt in zip(rids, prompts):
            assert out[rid] == eager_reference(model, prompt, budget), \
                f"stream diverged for rid={rid}"
    finally:
        for c in clients:
            c.close()


@pytest.mark.slow
def test_cross_socket_migration_byte_identical(fleet_pool,
                                               eager_oracle):
    """extract_request on process A -> KVPageBuffer over the wire ->
    inject_request on process B; the continuation is byte-identical to
    the uninterrupted eager stream (zero re-prefill resume)."""
    procs, addrs = fleet_pool
    cfg, model, eager_reference = eager_oracle
    a, b = _fleet_clients(addrs)
    try:
        prompt = _fleet_prompts(cfg.vocab_size, n=1, rng_seed=11)[0]
        budget = 6
        ref = eager_reference(model, prompt, budget)
        erid = a.add_request(prompt, max_new_tokens=budget)
        gen = []
        while len(gen) < 2:
            a.step()
            view = next((v for v in a.slots + a.waiting
                         if v.req_id == erid), None)
            assert view is not None
            gen = list(view.output_ids)
        _prompt, gen, buf = a.extract_request(erid)
        assert buf is not None and buf.n_tokens >= len(prompt)
        assert gen == ref[:len(gen)]
        resume = np.concatenate([prompt, np.asarray(gen, np.int64)])
        erid_b = b.inject_request(resume, buf,
                                  max_new_tokens=budget - len(gen))
        while b.has_work():
            b.step()
        cont = b.finished.pop(erid_b).output_ids
        assert gen + cont == ref
    finally:
        a.close()
        b.close()


@pytest.mark.slow
def test_fault_injected_hang_deadline_drain(eager_oracle):
    """A server process whose RPC plane hangs mid-run: the client's
    deadline fires, retries exhaust, and the router drains the engine
    and finishes everything on the survivor — no wedged router step."""
    cfg, model, eager_reference = eager_oracle
    procs, addrs = _spawn_pool(1)
    hang_procs, hang_addrs = _spawn_pool(
        1, cfg_overrides={"engine_id": 66,
                          # hit 1 = hello; the hang arms on a later
                          # frame, landing on an add/step with work
                          # already in flight on this engine
                          "fault_spec":
                          "hang:rpc.recv:ms=60000:after=4"})
    clients = _fleet_clients(addrs, step_timeout=240.0) + \
        _fleet_clients(hang_addrs, step_timeout=8.0)
    # the drain path (extract -> fallback) must also be bounded against
    # the hung server, not wait out the migration-sized deadlines
    clients[1]._timeouts.update({"add_request": 8.0,
                                 "extract_request": 8.0,
                                 "preempt_request": 8.0,
                                 "health_payload": 4.0})
    try:
        router = ServingRouter(clients, probe_failure_threshold=2)
        prompts = _fleet_prompts(cfg.vocab_size, n=4, rng_seed=7)
        budget = 4
        t0 = time.monotonic()
        rids = [router.submit(p, max_new_tokens=budget)
                for p in prompts]
        out = router.run_to_completion()
        assert sorted(out) == sorted(rids)
        for rid, prompt in zip(rids, prompts):
            assert out[rid] == eager_reference(model, prompt, budget)
        # bounded failure handling: deadline + retries, not the 60s
        # injected hang
        assert time.monotonic() - t0 < 180.0
        assert not router.handles[66].healthy
    finally:
        for c in clients:
            c.close()
        for p in procs + hang_procs:
            p.kill()


@pytest.mark.slow
def test_kill9_drill_zero_drops(fleet_pool, eager_oracle):
    """SIGKILL a real engine-server subprocess mid-decode: zero dropped
    requests, completed streams byte-identical to the eager reference,
    >=1 requeue{reason=engine_lost}, survivor pool leak-free, span
    chains valid.  Runs LAST against the module pool (it eats one of
    its processes)."""
    from paddle_tpu.observability.metrics import default_registry
    from paddle_tpu.observability.request_trace import \
        validate_span_chain
    procs, addrs = fleet_pool
    cfg, model, eager_reference = eager_oracle
    clients = _fleet_clients(addrs)
    m = default_registry().get("router_requeues_total")
    before = {ch.labels["reason"]: ch.value
              for ch in m.children()} if m else {}
    try:
        router = ServingRouter(clients, probe_failure_threshold=2)
        prompts = _fleet_prompts(cfg.vocab_size, n=4, rng_seed=23)
        budget = 5
        rids = [router.submit(p, max_new_tokens=budget)
                for p in prompts]
        stepped = 0
        while stepped < 2 and router.has_work():
            router.step()
            stepped += 1
        victim = next(
            h.engine_id for h in router.handles.values()
            if any(k[0] == h.engine_id for k in router._inflight))
        victim_proc = procs[
            [c.engine_id for c in clients].index(victim)]
        victim_proc.kill()                 # SIGKILL, mid-decode
        out = router.run_to_completion()
        assert sorted(out) == sorted(rids), "dropped request(s)"
        for rid, prompt in zip(rids, prompts):
            assert out[rid] == eager_reference(model, prompt, budget)
        m = default_registry().get("router_requeues_total")
        after = {ch.labels["reason"]: ch.value for ch in m.children()}
        assert after.get("engine_lost", 0) > \
            before.get("engine_lost", 0)
        for rid in rids:
            ok, why = validate_span_chain(router.tracer.events(rid))
            assert ok, f"rid={rid}: {why}"
        # survivor drained leak-free (prefix cache off in this rig)
        survivor = next(c for c in clients
                        if c.engine_id != victim)
        hp = survivor.health_payload()
        assert hp["free_pages"] == hp["total_pages"]
        assert hp["occupancy"] == 0 and hp["waiting"] == 0
    finally:
        for c in clients:
            c.close()
