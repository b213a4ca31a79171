"""Multi-process serving fleet bench: RPC overhead, parity, failure
drills.

Round-23 tentpole artifact (BENCH_FLEET_r23.json):

1. **Router overhead** (GATED < 2%) under the r16 same-pool paired
   protocol: the SAME multi-process pool (>= 2 real engine-server
   subprocesses) is driven either by the full ``ServingRouter``
   (affinity admission, probes, dispatch records, begin/finish
   fan-out) or by a minimal direct-drive loop (round-robin
   ``add_request`` + ``step`` until drained) — both arms pay the
   identical wire cost, so the trimmed mean of per-wave paired ratios
   isolates what the ROUTER layer adds per request on a real fleet.

1b. **Data-plane tax** (REPORTED, not gated): ONE warmed 2-engine
   pool, each engine ALSO served by an in-process ``EngineServer`` on
   loopback, arms toggling between direct in-process driving and
   ``RemoteEngineClient`` sockets.  This charges the full serialized
   RPC cost (framing, syscalls, dedup bookkeeping, thread handoff)
   against the tiny CPU model's ~4ms step wall; on a 1-core host no
   compute overlap is possible, so the ratio is reported honestly as
   the wire tax, not gated.

2. **Subprocess parity**: >= 2 REAL engine-server processes
   (``tools/engine_server.py`` via ``EngineProcess``) serve byte-
   identical token streams vs the SAME pool built in-process from the
   identical config (``build_engine_from_config`` — same seed, same
   weights), and vs the eager oracle.

3. **Cross-socket migration**: ``extract_request`` on process A ->
   ``KVPageBuffer`` over the wire -> ``inject_request`` on process B
   resumes FASTER than the re-prefill resume of the same-shape
   request, with a byte-identical continuation.

4. **kill -9 drill**: SIGKILL one server process mid-decode.  Gates:
   zero drops, byte parity, >= 1 requeue{reason=engine_lost}, every
   span chain validates, the survivor drains leak-free.

5. **Fault drills**: injected network faults (drop / econnreset /
   delay at the ``rpc.*`` sites) resolve as retry-then-success — every
   request completes, retries are observed, no wedged router step.

Model: the tiny llama config on CPU (artifact schema CI-checkable);
the 1.1B bench line on TPU.  Run from the repo root; artifact path in
argv[1] (default BENCH_FLEET_r23.json).  On any error ONE parseable
failure-marker JSON line is emitted and the run exits 1.
"""
import gc
import json
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax  # noqa: E402

from paddle_tpu.models.llama import param_count  # noqa: E402
from paddle_tpu.inference.fleet import (EngineProcess,  # noqa: E402
                                        EngineServer, RemoteEngineClient,
                                        RetryPolicy)
from paddle_tpu.inference.router import ServingRouter  # noqa: E402
from paddle_tpu.observability import validate_span_chain  # noqa: E402
from paddle_tpu.observability.metrics import default_registry  # noqa: E402
from paddle_tpu.testing import faults  # noqa: E402
from tools.bench_common import (build_bench_model,  # noqa: E402
                                eager_reference, make_engines,
                                warm_engines)
from tools.engine_server import build_engine_from_config  # noqa: E402

OVERHEAD_GATE = 0.02
OVERHEAD_BUDGET = 16          # decode tokens/request in the overhead arm


def _wave_prompts(knobs, vocab, n, seed):
    rng = np.random.RandomState(seed)
    L = knobs["prefix_len"] + knobs["suffix_len"]
    return [rng.randint(1, vocab, (L,)).astype(np.int64)
            for _ in range(n)]


def _fleet_clients(addrs, step_timeout=240.0, **extra):
    t = {"step": step_timeout, "add_request": 60.0, "hello": 60.0,
         "extract_request": 120.0, "inject_request": 240.0,
         "preempt_request": 60.0, "health_payload": 10.0}
    t.update(extra)
    return [RemoteEngineClient(
        a, retry=RetryPolicy(max_attempts=3, base_delay=0.05,
                             max_delay=0.5), timeouts=t)
        for a in addrs]


def _requeue_count(reason):
    m = default_registry().get("router_requeues_total")
    if m is None:
        return 0
    return sum(ch.value for ch in m.children()
               if ch.labels.get("reason") == reason)


def _retry_total():
    m = default_registry().get("router_rpc_retries_total")
    if m is None:
        return 0
    return sum(ch.value for ch in m.children())


# ---------------------------------------------------------------------------
# 1. router overhead (same-pool paired toggle, GATED) — and
# 1b. data-plane tax (loopback, REPORTED)
# ---------------------------------------------------------------------------
def bench_router_overhead(model, knobs, addrs, waves=13):
    """The r16 paired protocol on the REAL subprocess pool: each wave
    runs the same prompts through (a) the full ``ServingRouter`` and
    (b) a minimal direct-drive loop over the same clients.  Both arms
    pay the identical wire cost; the paired ratio is what the router
    layer itself adds per request."""
    vocab = model.config.vocab_size
    n = knobs["families"] * knobs["per_family"]
    clients = _fleet_clients(addrs)

    def run_router(prompts):
        router = ServingRouter(clients, probe_failure_threshold=3)
        rids = [router.submit(p, max_new_tokens=OVERHEAD_BUDGET)
                for p in prompts]
        router.run_to_completion()
        for rid in rids:
            router.pop_record(rid)

    def run_direct(prompts):
        erids = []
        for i, p in enumerate(prompts):
            cli = clients[i % len(clients)]
            erids.append((cli, cli.add_request(
                p, max_new_tokens=OVERHEAD_BUDGET)))
        while any(c.has_work() for c in clients):
            for c in clients:
                c.step()
        for cli, erid in erids:
            cli.finished.pop(erid)

    try:
        # one unmeasured preseed through each arm (cold dispatch paths)
        run_router(_wave_prompts(knobs, vocab, n, seed=41))
        run_direct(_wave_prompts(knobs, vocab, n, seed=43))
        times = {"router": [], "direct": []}
        for w in range(waves):
            prompts = _wave_prompts(knobs, vocab, n, seed=100 + w)
            for arm in (("router", "direct") if w % 2 == 0
                        else ("direct", "router")):
                gc.collect()
                t0 = time.perf_counter()
                (run_router if arm == "router" else run_direct)(prompts)
                times[arm].append(time.perf_counter() - t0)
        ratios = sorted(a / max(1e-12, b)
                        for a, b in zip(times["router"], times["direct"]))
        trim = len(ratios) // 4
        kept = ratios[trim:len(ratios) - trim] or ratios
        overhead = sum(kept) / len(kept) - 1.0
        med_r = statistics.median(times["router"])
        med_d = statistics.median(times["direct"])
        return {
            "waves": waves, "budget": OVERHEAD_BUDGET,
            "requests_per_wave": n,
            "median_wall_router_s": round(med_r, 4),
            "median_wall_direct_s": round(med_d, 4),
            "per_request_overhead_ms":
                round((med_r - med_d) / n * 1000.0, 3),
            "per_wave_ratios": [round(r - 1.0, 4) for r in ratios],
            "wall_router_s": [round(t, 4) for t in times["router"]],
            "wall_direct_s": [round(t, 4) for t in times["direct"]],
            "overhead_ratio": round(overhead, 4),
            "overhead_gate": OVERHEAD_GATE,
            "method": "same-pool router/direct toggle on the live "
                      "subprocess fleet, same prompts per wave, strict "
                      "first-runner alternation; gate on trimmed mean "
                      "of per-wave paired ratios",
        }
    finally:
        for c in clients:
            c.close()


def bench_data_plane(model, knobs, waves=13):
    """The r16 design ported to the wire layer, REPORTED not gated: the
    SAME two engines are driven either directly or through loopback
    EngineServers, so a wave's paired ratio charges the full serialized
    RPC cost against the tiny model's step wall.  The remote arm also
    exercises the begin_step/finish_step fan-out."""
    vocab = model.config.vocab_size
    engines = make_engines(model, 2, knobs, id_base=0)
    warm_engines(engines, knobs, vocab)
    servers = [EngineServer(e, idle_poll_s=0.05).start() for e in engines]
    clients = _fleet_clients([s.address for s in servers])
    router_in = ServingRouter(engines)
    router_remote = ServingRouter(clients)
    n = knobs["families"] * knobs["per_family"]
    try:
        # unmeasured preseed through EACH arm: warms both routers'
        # dispatch paths and syncs the remote prefix-table view
        for seed, router in ((41, router_remote), (43, router_in)):
            for p in _wave_prompts(knobs, vocab, n, seed):
                router.submit(p, max_new_tokens=knobs["budget"])
            router.run_to_completion()
        times = {"remote": [], "in": []}
        for w in range(waves):
            for pos, arm in enumerate(("remote", "in") if w % 2 == 0
                                      else ("in", "remote")):
                router = router_remote if arm == "remote" else router_in
                prompts = _wave_prompts(knobs, vocab, n,
                                        seed=100 + 2 * w + pos)
                gc.collect()
                t0 = time.perf_counter()
                rids = [router.submit(p, max_new_tokens=OVERHEAD_BUDGET)
                        for p in prompts]
                router.run_to_completion()
                times[arm].append(time.perf_counter() - t0)
                for rid in rids:
                    router.pop_record(rid)
        ratios = sorted(a / max(1e-12, b)
                        for a, b in zip(times["remote"], times["in"]))
        trim = len(ratios) // 4
        kept = ratios[trim:len(ratios) - trim] or ratios
        tax = sum(kept) / len(kept) - 1.0
        med_r = statistics.median(times["remote"])
        med_i = statistics.median(times["in"])
        per_req_ms = (med_r - med_i) / n * 1000.0
        return {
            "waves": waves, "budget": OVERHEAD_BUDGET,
            "requests_per_wave": n,
            "median_wall_remote_s": round(med_r, 4),
            "median_wall_inproc_s": round(med_i, 4),
            "per_request_tax_ms": round(per_req_ms, 3),
            "per_wave_ratios": [round(r - 1.0, 4) for r in ratios],
            "wall_remote_s": [round(t, 4) for t in times["remote"]],
            "wall_inproc_s": [round(t, 4) for t in times["in"]],
            "tax_ratio": round(tax, 4),
            "gated": False,
            "note": "full serialized RPC cost vs the tiny model's ~4ms "
                    "CPU step wall on a 1-core host (no compute "
                    "overlap possible); reported for transparency, the "
                    "gated router-overhead metric is the same-pool "
                    "router/direct toggle on the subprocess fleet",
            "method": "same-pool remote/in-process toggle, waves "
                      "interleaved; trimmed mean of per-wave paired "
                      "ratios",
        }, (engines, servers, clients)
    except Exception:
        for c in clients:
            c.close()
        for s in servers:
            s.stop()
        raise


# ---------------------------------------------------------------------------
# 5. fault drills (runs on the overhead rig's servers)
# ---------------------------------------------------------------------------
def bench_fault_drills(model, knobs, rig):
    """Each drill installs one network-fault spec, runs a small wave
    through a tight-deadline remote router, and requires completion +
    parity; the transient drills must also show retries.  The injector
    is process-global and the servers are in-process threads here, so
    the faults land on whichever side hits the site — both sides of
    the wire are exercised across the drills."""
    engines, servers, _ = rig
    vocab = model.config.vocab_size
    clients = _fleet_clients(
        [s.address for s in servers], step_timeout=5.0,
        add_request=5.0, health_payload=2.0)
    drills = [
        ("drop_request", "drop:rpc.send:after=3:times=1", True),
        ("drop_reply", "drop:rpc.send:after=8:times=1", True),
        ("econnreset", "econnreset:rpc.recv:after=2:times=1", True),
        ("delay", "delay:rpc.send:ms=50:after=1:times=4", False),
    ]
    results = []
    try:
        for di, (name, spec, wants_retry) in enumerate(drills):
            router = ServingRouter(clients, probe_failure_threshold=3)
            prompts = _wave_prompts(knobs, vocab, 3, seed=700 + di)
            retries0 = _retry_total()
            faults.configure(spec)
            t0 = time.perf_counter()
            rids = [router.submit(p, max_new_tokens=knobs["budget"])
                    for p in prompts]
            out = router.run_to_completion()
            wall = time.perf_counter() - t0
            faults.configure(None)
            parity = all(out.get(rid) == eager_reference(
                model, p, knobs["budget"])
                for rid, p in zip(rids, prompts))
            retried = _retry_total() - retries0
            results.append({
                "drill": name, "spec": spec,
                "completed": len(out) == len(rids),
                "token_parity": bool(parity),
                "retries_observed": int(retried),
                "needs_retry": wants_retry,
                "wall_s": round(wall, 3),
                "resolved": bool(len(out) == len(rids) and parity
                                 and (retried > 0 or not wants_retry)),
            })
    finally:
        faults.configure(None)
        for c in clients:
            c.close()
    return results


# ---------------------------------------------------------------------------
# 2. subprocess parity
# ---------------------------------------------------------------------------
def _proc_config(knobs, engine_id):
    return {"platform": "cpu", "seed": 0, "engine_id": engine_id,
            "slots": knobs["slots"], "num_blocks": knobs["num_blocks"],
            "block_size": knobs["block_size"], "chunk": knobs["chunk"],
            "enable_prefix_cache": False,
            "warm": {"prompt_len": knobs["prefix_len"]
                     + knobs["suffix_len"], "budget": knobs["budget"]}}


def bench_subprocess_parity(model, knobs, procs, addrs):
    """The headline robustness parity: >= 2 real server processes vs
    the identical pool in-process vs the eager oracle, byte for byte."""
    vocab = model.config.vocab_size
    budget = knobs["budget"] + 2
    prompts = _wave_prompts(knobs, vocab, 6, seed=301)

    clients = _fleet_clients(addrs)
    try:
        router = ServingRouter(clients)
        rids = [router.submit(p, max_new_tokens=budget) for p in prompts]
        remote_out = router.run_to_completion()
        remote = [remote_out[r] for r in rids]
        engines_used = set()
        for r in rids:
            engines_used.update(router.finished[r].engines_visited())
    finally:
        for c in clients:
            c.close()

    # the same pool, in-process, from the IDENTICAL configs (platform
    # "inherit" skips the subprocess-only device re-forcing — jax is
    # already configured in this process and tearing down the live
    # backends under the warmed model would invalidate it)
    pool = [build_engine_from_config(
        {**_proc_config(knobs, 40 + i), "platform": "inherit"})[1]
        for i in range(len(addrs))]
    router_in = ServingRouter(pool)
    rids_in = [router_in.submit(p, max_new_tokens=budget)
               for p in prompts]
    in_out = router_in.run_to_completion()
    inproc = [in_out[r] for r in rids_in]

    oracle = [eager_reference(model, p, budget) for p in prompts]
    return {
        "processes": len(addrs), "requests": len(prompts),
        "budget": budget,
        "engines_used": sorted(engines_used),
        "remote_vs_inprocess": remote == inproc,
        "remote_vs_eager": remote == oracle,
        "both_processes_served": len(engines_used) >= 2,
    }


# ---------------------------------------------------------------------------
# 3. cross-socket migration vs re-prefill
# ---------------------------------------------------------------------------
def _resume_pair(model, knobs, a, b, seed, budget, take):
    """Decode ``take`` tokens on A, extract, and return everything the
    two resume paths need on B."""
    vocab = model.config.vocab_size
    prompt = _wave_prompts(knobs, vocab, 1, seed)[0]
    erid = a.add_request(prompt, max_new_tokens=budget)
    gen = []
    while len(gen) < take:
        a.step()
        view = next((v for v in a.slots + a.waiting
                     if v.req_id == erid), None)
        gen = list(view.output_ids) if view is not None else gen
    _p, gen, buf = a.extract_request(erid)
    resume = np.concatenate([prompt, np.asarray(gen, np.int64)])
    return prompt, gen, buf, resume


def _drain_first_token(cli, erid, t0):
    """Steps until the injected/re-added request emits one token, then
    runs it to completion; returns (first_token_s since ``t0``,
    output_ids).  ``t0`` predates the inject/add RPC, so the inject
    path's page-transfer cost and the re-prefill path's prefill steps
    are both inside the measured window."""
    t_first = None
    for _ in range(200):
        cli.step()
        if erid in cli.finished:
            if t_first is None:
                t_first = time.perf_counter() - t0
            break
        view = next((v for v in cli.slots + cli.waiting
                     if v.req_id == erid), None)
        if t_first is None and view is not None and view.output_ids:
            t_first = time.perf_counter() - t0
        if view is None:
            break
    while cli.has_work():
        cli.step()
    rec = cli.finished.pop(erid)
    return t_first, rec.output_ids


def bench_migration(model, knobs, addrs, trials=3):
    """Paired resume timing on process B for requests preempted off
    process A: inject (KV pages over the wire, zero re-prefill) vs
    re-prefill (resume prompt through add_request).  One unmeasured
    warm pair first so neither measured path eats a cold compile."""
    budget, take = knobs["budget"] + 2, 2
    a, b = _fleet_clients(addrs)
    inj_t, pre_t = [], []
    parity = True
    try:
        for trial in range(trials + 1):
            measured = trial > 0
            seed = 400 + 10 * trial
            # inject path
            prompt, gen, buf, resume = _resume_pair(
                model, knobs, a, b, seed, budget, take)
            t0 = time.perf_counter()
            erid = b.inject_request(resume, buf,
                                    max_new_tokens=budget - len(gen))
            tf, cont = _drain_first_token(b, erid, t0)
            if measured:
                inj_t.append(tf if tf is not None
                             else time.perf_counter() - t0)
                ref = eager_reference(model, prompt, budget)
                parity = parity and (gen + cont == ref)
            # re-prefill path (same shape, fresh prompt)
            prompt2, gen2, _buf2, resume2 = _resume_pair(
                model, knobs, a, b, seed + 1, budget, take)
            t0 = time.perf_counter()
            erid2 = b.add_request(resume2, max_new_tokens=budget
                                  - len(gen2))
            tf2, cont2 = _drain_first_token(b, erid2, t0)
            if measured:
                pre_t.append(tf2 if tf2 is not None
                             else time.perf_counter() - t0)
                ref2 = eager_reference(model, prompt2, budget)
                parity = parity and (gen2 + cont2 == ref2)
    finally:
        a.close()
        b.close()
    med_inj = statistics.median(inj_t)
    med_pre = statistics.median(pre_t)
    return {
        "trials": trials,
        "resume_first_token_inject_s": [round(t, 4) for t in inj_t],
        "resume_first_token_reprefill_s": [round(t, 4) for t in pre_t],
        "median_inject_s": round(med_inj, 4),
        "median_reprefill_s": round(med_pre, 4),
        "inject_speedup": round(med_pre / max(1e-12, med_inj), 3),
        "migration_faster": med_inj < med_pre,
        "continuation_parity": bool(parity),
    }


# ---------------------------------------------------------------------------
# 4. kill -9 drill
# ---------------------------------------------------------------------------
def bench_kill_drill(model, knobs, procs, addrs):
    vocab = model.config.vocab_size
    budget = knobs["budget"] + 2
    prompts = _wave_prompts(knobs, vocab, 6, seed=501)
    clients = _fleet_clients(addrs)
    requeues0 = _requeue_count("engine_lost")
    try:
        router = ServingRouter(clients, probe_failure_threshold=2)
        rids = [router.submit(p, max_new_tokens=budget) for p in prompts]
        for _ in range(2):
            router.step()
        victim = next(h.engine_id for h in router.handles.values()
                      if any(k[0] == h.engine_id
                             for k in router._inflight))
        procs[[c.engine_id for c in clients].index(victim)].kill()
        t0 = time.perf_counter()
        out = router.run_to_completion()
        drain_wall = time.perf_counter() - t0
        zero_drops = sorted(out) == sorted(rids)
        parity = all(out[rid] == eager_reference(model, p, budget)
                     for rid, p in zip(rids, prompts))
        chain_failures = []
        for rid in rids:
            ok, why = validate_span_chain(router.tracer.events(rid))
            if not ok:
                chain_failures.append({"rid": rid, "why": why})
        survivor = next(c for c in clients if c.engine_id != victim)
        hp = survivor.health_payload()
        leak_free = (hp["free_pages"] == hp["total_pages"]
                     and hp["occupancy"] == 0 and hp["waiting"] == 0)
        return {
            "requests": len(prompts), "budget": budget,
            "victim_engine": int(victim),
            "zero_drops": bool(zero_drops),
            "token_parity": bool(parity),
            "engine_lost_requeues":
                int(_requeue_count("engine_lost") - requeues0),
            "chain_failures": chain_failures,
            "survivor_leak_free": bool(leak_free),
            "survivor_pages": {"free": int(hp["free_pages"]),
                               "total": int(hp["total_pages"])},
            "drain_wall_s": round(drain_wall, 3),
        }
    finally:
        for c in clients:
            c.close()


def main(out_path):
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    cfg, model = build_bench_model(on_tpu)
    if on_tpu:
        knobs = dict(slots=4, num_blocks=512, block_size=16, chunk=64,
                     prefix_len=192, suffix_len=32, families=6,
                     per_family=4, budget=16)
        waves = 13
    else:
        knobs = dict(slots=2, num_blocks=96, block_size=4, chunk=8,
                     prefix_len=24, suffix_len=4, families=5,
                     per_family=3, budget=4)
        waves = 13

    ok = True
    gate_notes = []

    data_plane, rig = bench_data_plane(model, knobs, waves=waves)
    print("# data plane (ungated): median remote=%.3fs inproc=%.3fs "
          "tax_ratio=%.4f (%.2fms/request serialized wire tax)"
          % (data_plane["median_wall_remote_s"],
             data_plane["median_wall_inproc_s"],
             data_plane["tax_ratio"],
             data_plane["per_request_tax_ms"]),
          file=sys.stderr)

    drills = bench_fault_drills(model, knobs, rig)
    for c in rig[2]:
        c.close()
    for s in rig[1]:
        s.stop()
    for d in drills:
        print("# drill %-13s resolved=%s retries=%d wall=%.2fs"
              % (d["drill"], d["resolved"], d["retries_observed"],
                 d["wall_s"]), file=sys.stderr)
        if not d["resolved"]:
            ok = False
            gate_notes.append("fault drill %s unresolved: %r"
                              % (d["drill"], d))

    procs = [EngineProcess(_proc_config(knobs, 10 + i),
                           env={"JAX_PLATFORMS": "cpu"},
                           startup_timeout=600.0) for i in range(2)]
    try:
        addrs = [p.spawn() for p in procs]

        overhead = bench_router_overhead(model, knobs, addrs, waves=waves)
        print("# router overhead: median router=%.3fs direct=%.3fs "
              "ratio=%.4f (%.2fms/request; gate < %.2f)"
              % (overhead["median_wall_router_s"],
                 overhead["median_wall_direct_s"],
                 overhead["overhead_ratio"],
                 overhead["per_request_overhead_ms"], OVERHEAD_GATE),
              file=sys.stderr)
        if overhead["overhead_ratio"] >= OVERHEAD_GATE:
            ok = False
            gate_notes.append("router overhead %.4f >= %.2f"
                              % (overhead["overhead_ratio"],
                                 OVERHEAD_GATE))

        parity = bench_subprocess_parity(model, knobs, procs, addrs)
        print("# parity: remote==inproc=%s remote==eager=%s engines=%r"
              % (parity["remote_vs_inprocess"],
                 parity["remote_vs_eager"], parity["engines_used"]),
              file=sys.stderr)
        if not (parity["remote_vs_inprocess"]
                and parity["remote_vs_eager"]
                and parity["both_processes_served"]):
            ok = False
            gate_notes.append("subprocess parity failed: %r" % parity)

        migration = bench_migration(model, knobs, addrs)
        print("# migration: inject=%.3fs reprefill=%.3fs speedup=%.2fx "
              "parity=%s"
              % (migration["median_inject_s"],
                 migration["median_reprefill_s"],
                 migration["inject_speedup"],
                 migration["continuation_parity"]), file=sys.stderr)
        if not (migration["migration_faster"]
                and migration["continuation_parity"]):
            ok = False
            gate_notes.append("migration gate failed: %r" % migration)

        drill = bench_kill_drill(model, knobs, procs, addrs)
        print("# kill drill: drops=%s parity=%s requeues=%d chains=%s "
              "leak_free=%s"
              % (not drill["zero_drops"], drill["token_parity"],
                 drill["engine_lost_requeues"],
                 not drill["chain_failures"],
                 drill["survivor_leak_free"]), file=sys.stderr)
        if not (drill["zero_drops"] and drill["token_parity"]
                and drill["engine_lost_requeues"] >= 1
                and not drill["chain_failures"]
                and drill["survivor_leak_free"]):
            ok = False
            gate_notes.append("kill drill failed: %r"
                              % {k: drill[k] for k in
                                 ("zero_drops", "token_parity",
                                  "engine_lost_requeues",
                                  "survivor_leak_free")})
    finally:
        for p in procs:
            p.kill()

    artifact = {
        "metric": "fleet_router_overhead_ratio",
        "value": overhead["overhead_ratio"],
        "passed": ok,
        "gate_notes": gate_notes,
        "overhead": overhead,
        "data_plane": data_plane,
        "fault_drills": drills,
        "subprocess_parity": parity,
        "migration": migration,
        "kill_drill": drill,
        "config": {
            "params_m": round(param_count(cfg) / 1e6),
            "layers": cfg.num_hidden_layers,
            "hidden": cfg.hidden_size,
            "dtype": cfg.dtype,
            **knobs,
        },
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps({
        "metric": artifact["metric"],
        "value": artifact["value"],
        "unit": "overhead_ratio",
        "vs_baseline": (OVERHEAD_GATE - overhead["overhead_ratio"]
                        if ok else 0.0),
    }), flush=True)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    out = sys.argv[1] if len(sys.argv) > 1 else "BENCH_FLEET_r23.json"
    try:
        main(out)
    except SystemExit:
        raise
    except Exception as e:                            # noqa: BLE001
        print(json.dumps({
            "metric": "fleet_router_overhead_ratio",
            "value": 1.0,
            "unit": "error",
            "vs_baseline": 0.0,
            "error": f"{type(e).__name__}: {e}",
        }), flush=True)
        raise SystemExit(1)
