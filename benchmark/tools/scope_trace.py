#!/usr/bin/env python3
"""Where a cell's device time goes by PART OF THE STEP, and which phase of
``engine.step()`` owns each idle gap.  Like ``record_trace.py`` it is not
part of a run: it builds the cell's engine, offers the cell's own traffic,
traces a few seconds of it under the profiler and KEEPS the ``.xplane.pb``.

    python3 benchmark/tools/scope_trace.py --workload <cell> --seed 1 \
        --seconds 3 --out chiprun_out/scope/<cell>

It joins three things the program writes since PR 24:

- the ``engine.step`` profiler span of every step (``step_num`` = the
  step's index) and its six phase spans ``engine.admit | pack | fill |
  dispatch | fetch | book``, on the device trace's own clock;
- the step records (``harness/program_spans.py``): the step of that index
  says which token budget it launched and whether it carried a chunk;
- ``MixedStep.op_scopes(budget)``: the named scope of every instruction of
  that budget's compiled module, by the instruction name a device event
  carries.

Printed, and written to ``<out>/scope_trace.json``: device seconds by
scope for decode-only steps and for steps with a chunk, the share of busy
time with no scope, and the idle gaps over 1 ms by the phases they overlap
(``idle_gaps`` says what that split can and cannot be trusted for).
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench_run                                 # noqa: E402
from harness import (program, program_spans, serve, spec,   # noqa: E402
                     traffic, xtrace)

NO_SCOPE = "(no scope)"
GAP_FLOOR_NS = 1_000_000          # the gaps worth an owner: over 1 ms


def instruction(event_name: str) -> str:
    """``%fusion.12 = f32[..] fusion(...)`` -> ``fusion.12``: the name
    ``op_scopes`` keys by (``xtrace.op_name`` drops the instance)."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def device_events(profile):
    """Leaf ``(instruction, start_ns, end_ns)`` of the first device;
    none where the trace has no device plane (the CPU, in tests)."""
    for plane in profile.planes:
        if not plane.name.startswith(xtrace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == xtrace.OPS_LINE:
                return xtrace._leaves(
                    [(instruction(ev.name), ev.start_ns,
                      ev.start_ns + ev.duration_ns) for ev in line.events])
    return []


def host_events(profile):
    """``engine.step`` spans as ``(start, end, step_num)`` and the phase
    spans as ``(start, end, name)``, both sorted."""
    steps, phases = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith("engine."):
                    continue
                span = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == "engine.step":
                    steps.append(span + (int(dict(ev.stats)["step_num"]),))
                else:
                    phases.append(span + (ev.name,))
    return sorted(steps), sorted(phases)


def by_scope(ops, steps, records, scopes_of):
    """``{step kind: {scope: seconds}}``: each device op goes to the
    ``engine.step`` span that holds its start, that step's record says
    which budget ran and whether it carried a chunk."""
    starts = [s for s, _, _ in steps]
    out = {"decode": {}, "chunk": {}, "outside a step": {}}
    detail = {}
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        rec = None
        if i >= 0 and s < steps[i][1]:
            rec = records.get(steps[i][2])
        if rec is None or not rec["budget"]:
            kind, scope = "outside a step", NO_SCOPE
        else:
            kind = "chunk" if rec["n_pre"] > 0 else "decode"
            scope = scopes_of(rec["budget"]).get(name) or NO_SCOPE
        sec = (e - s) / 1e9
        out[kind][scope] = out[kind].get(scope, 0.0) + sec
        key = (scope, xtrace.op_name(name))
        detail[key] = detail.get(key, 0.0) + sec
    return out, detail


def idle_gaps(ops, phases):
    """The device's idle gaps of over 1 ms, each split over the phase
    spans that overlap it AS TRACED (``seconds_by_phase``; ``owners``
    counts the gaps by the phase holding most of each).

    As traced is not as happened: device events carry the device's
    clock, which by causality leads the host spans' by something between
    ``clock_lead_ms``'s two ends.  A launch cannot start on the device
    before the ``engine.dispatch`` that enqueues it begins (the largest
    such violation is the least lead), and its tokens cannot reach the
    host before the device has finished (the smallest ``engine.fetch``
    end minus device end is the most).  What needs no common clock is
    in ``median_ms``: the gap itself (device clock) against the host's
    time from that fetch's end to the next dispatch's end, both as
    durations; the rest of the gap is transfer and launch latency that
    no phase's Python fills."""
    merged = xtrace._merge([(s, e) for _, s, e in ops])
    starts = [a for a, _, _ in phases]
    fetches = [(a, b) for a, b, name in phases if name == "engine.fetch"]
    dispatches = [(a, b) for a, b, name in phases
                  if name == "engine.dispatch"]
    fetch_starts = [a for a, _ in fetches]
    dispatch_starts = [a for a, _ in dispatches]
    split, owners = {}, {}
    small = [0, 0.0]
    lengths, host_between, lead_lo, lead_hi = [], [], [], []
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        if s1 - e0 <= GAP_FLOOR_NS:
            small[0] += 1
            small[1] += (s1 - e0) / 1e9
            continue
        lengths.append((s1 - e0) / 1e6)
        best, who, covered = 0, "between steps", 0
        i = max(0, bisect.bisect_right(starts, e0) - 1)
        while i < len(phases) and phases[i][0] < s1:
            a, b, name = phases[i]
            over = min(b, s1) - max(a, e0)
            if over > 0:
                split[name] = split.get(name, 0.0) + over / 1e9
                covered += over
                if over > best:
                    best, who = over, name
            i += 1
        split["between steps"] = split.get("between steps", 0.0) \
            + (s1 - e0 - covered) / 1e9
        owners[who] = owners.get(who, 0) + 1
        k = bisect.bisect_right(fetch_starts, e0) - 1
        if k < 0:
            continue
        fetch_end = fetches[k][1]
        d = bisect.bisect_left(dispatch_starts, fetch_end)
        if d < len(dispatches):
            lead_hi.append((fetch_end - e0) / 1e6)
            lead_lo.append((dispatches[d][0] - s1) / 1e6)
            host_between.append((dispatches[d][1] - fetch_end) / 1e6)

    def median(values):
        values = sorted(values)
        return values[len(values) // 2] if values else None

    return {
        "over_1ms": len(lengths), "seconds": sum(lengths) / 1e3,
        "under_1ms": {"gaps": small[0], "seconds": small[1]},
        "seconds_by_phase": dict(sorted(split.items(),
                                        key=lambda kv: -kv[1])),
        "owners": owners,
        "clock_lead_ms": [max(lead_lo, default=None),
                          min(lead_hi, default=None)],
        "median_ms": {"gap": median(lengths),
                      "host_fetch_end_to_dispatch_end":
                          median(host_between)},
    }


def trace_cell(workload: str, seed: int, seconds: float, out: str,
               root: str = spec.ROOT, require_chip: bool = True) -> dict:
    """Trace ``seconds`` of the cell's traffic into ``out`` and reduce
    it.  ``root`` and ``require_chip`` as in ``run.run_cell``: the tests
    run a tiny cell on the CPU, whose trace has host spans alone."""
    import jax
    if require_chip:
        program.enable_compile_cache()
    cell = spec.Cell(workload, root)
    cfg, deploy = cell.config, cell.deploy
    model = program.build_model(cell, seed)
    eng = program.build_engine(model, deploy["engine"])
    serve.warm_budgets(eng)
    shutil.rmtree(out, ignore_errors=True)
    start, stop, annotate = bench_run._tracer(out)
    plan = traffic.make_plan(dict(cell.traffic), deploy, cfg["vocab_size"],
                             seed, seconds + 1.0)
    drain, serve.DRAIN_LIMIT_S = serve.DRAIN_LIMIT_S, 0.0
    try:                  # a tool: what was offered need not run to its end
        serve.run_window(eng, plan, (0.0, seconds, start, stop), annotate)
    finally:
        serve.DRAIN_LIMIT_S = drain
    path = xtrace.find_xplane(out)
    profile = xtrace.load(path)
    ops = device_events(profile)
    if require_chip and not ops:
        raise ValueError("the trace has no device plane")
    steps, phases = host_events(profile)
    records = {r["step"]: r for r in program_spans.all_step_records()}
    cache = {}

    def scopes_of(budget):
        if budget not in cache:
            cache[budget] = eng.mixed.op_scopes(budget)
        return cache[budget]

    table, detail = by_scope(ops, steps, records, scopes_of)
    traced = [records[n] for _, _, n in steps if n in records]
    busy = sum(e - s for s, e in xtrace._merge(
        [(s, e) for _, s, e in ops])) / 1e9
    total = sum(sum(v.values()) for v in table.values())
    unknown = sum(v.get(NO_SCOPE, 0.0) for v in table.values())
    result = {
        "file": path, "bytes": os.path.getsize(path),
        "workload": workload, "seed": seed,
        "device": jax.devices()[0].device_kind,
        "steps": {"decode": sum(r["budget"] > 0 and r["n_pre"] == 0
                                for r in traced),
                  "chunk": sum(r["n_pre"] > 0 for r in traced),
                  "budgets": sorted({r["budget"] for r in traced})},
        "busy_s": busy, "op_seconds": total,
        "no_scope_share": unknown / total if total else None,
        "seconds_by_scope": {k: dict(sorted(v.items(),
                                            key=lambda kv: -kv[1]))
                             for k, v in table.items()},
        "top_ops": [[scope, op, sec] for (scope, op), sec in sorted(
            detail.items(), key=lambda kv: -kv[1])[:24]],
        "idle_gaps": idle_gaps(ops, phases),
    }
    with open(os.path.join(out, "scope_trace.json"), "w") as f:
        json.dump(result, f, indent=1)
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("scope_trace: needs a TPU", file=sys.stderr)
        return 2
    result = trace_cell(args.workload, args.seed, args.seconds, args.out)
    for kind, table in result["seconds_by_scope"].items():
        whole = sum(table.values())
        print(f"-- {kind}: {whole:.3f} s of device ops")
        for scope, sec in table.items():
            print(f"   {scope:16s} {sec:8.4f} s  {100 * sec / whole:5.1f}%")
    print(f"-- no scope: {100 * result['no_scope_share']:.2f}% of "
          f"{result['op_seconds']:.3f} s")
    idle = result["idle_gaps"]
    print(f"-- idle: {idle['over_1ms']} gaps over 1 ms, "
          f"{idle['seconds'] * 1e3:.2f} ms; as traced:")
    for who, sec in idle["seconds_by_phase"].items():
        print(f"   {who:16s} {sec * 1e3:8.2f} ms, holds most of "
              f"{idle['owners'].get(who, 0)} gaps")
    print(f"   device clock leads the host's by {idle['clock_lead_ms']} "
          f"ms; medians {idle['median_ms']}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
