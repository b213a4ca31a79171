#!/usr/bin/env python3
"""The benchmark: one run of one cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result (one JSON object).  With
no TPU, or fewer chips than the cell asks for, it exits 2 and prints no
result.  A compilation inside the measured window fails the run (exit 3).
Everything about a cell is data: see ``harness/spec.py``.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse          # noqa: E402
import gc                # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402

import numpy as np       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import spec                 # noqa: E402
from harness.peaks import peaks_for     # noqa: E402
from harness.result import Run          # noqa: E402

# A traced run lengthens its warm-up: the profiler starts when
# the normal warm-up ends, records ``trace_seconds`` of the same traffic,
# stops, and only then does the window open.  Starting and stopping the
# profiler each stall the thread that offers the load for seconds; inside
# the window that would be read as the generator's and the queue's delay.
TRACE_STALL_ALLOWANCE_S = 9.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_facts(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    dev = devs[0]
    if require_chip and (dev.platform != "tpu" or len(devs) < chips):
        raise SystemExit(_no_chip(dev.platform, len(devs), chips))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips if require_chip else len(devs)}


def _no_chip(platform, have, want) -> int:
    print(f"benchmark: the cell needs {want} TPU chip(s); JAX reports "
          f"{have} device(s) of platform {platform!r}", file=sys.stderr)
    return 2


def memory_peak(chips: int):
    import jax
    stats = [d.memory_stats() for d in jax.devices()[:chips]]
    if any(s is None for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def _tracer(trace_dir: str):
    import jax

    def start():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    return start, jax.profiler.stop_trace, jax.profiler.TraceAnnotation


def _reduce_trace(trace_dir, traced, step_span: str, require_chip: bool):
    """The traced part's reduction (``xtrace.reduce`` and its length), or
    ``None`` where nothing was traced."""
    from harness import xtrace
    if trace_dir is None or traced is None:
        return None
    try:
        red = xtrace.reduce(xtrace.load(xtrace.find_xplane(trace_dir)),
                            step_span)
    except ValueError:
        if require_chip:          # a trace with no device op in it
            raise
        return None               # the CPU has no device plane
    red["window_s"] = traced["t1"] - traced["t0"]
    return red


def _add_trace(result: dict, device: dict, run: Run) -> None:
    """``busy_s``, ``window_s`` and the breakdown of a traced run."""
    from harness import xtrace
    if run.trace is None:
        return
    device["busy_s"] = run.trace["busy_s"]
    device["window_s"] = run.trace["window_s"]
    result["breakdown"] = {"device_ops": xtrace.top_ops(run.trace),
                           "idle_gaps": run.trace["longest_gaps"]}
    result["idle_by_owner"] = run.trace["idle_by_owner"]


def run_serve(cell: spec.Cell, seed: int, seconds: float, trace: bool,
              require_chip: bool):
    """A serving cell."""
    from harness import correct, program, serve, traffic

    device = device_facts(cell.chips, require_chip)
    peaks = peaks_for(device["kind"]) if require_chip else None
    if require_chip:
        log(f"compile cache: {program.enable_compile_cache()}")
    cfg, deploy = cell.config, cell.deploy
    t = time.perf_counter()
    model = program.build_model(cell, seed)
    eng = program.build_engine(model, deploy["engine"])
    t_build = time.perf_counter() - t
    t = time.perf_counter()
    serve.warm_budgets(eng)
    t_compile = time.perf_counter() - t
    mix = dict(cell.traffic)
    trace_dir = trace_span = annotate = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        start, stop, annotate = _tracer(trace_dir)
        interlude = float(deploy["trace_seconds"]) + TRACE_STALL_ALLOWANCE_S
        trace_span = (-interlude, float(deploy["trace_seconds"]),
                      start, stop)
        mix["warmup_s"] = float(mix["warmup_s"]) + interlude
    plan = traffic.make_plan(mix, deploy, cfg["vocab_size"], seed, seconds)
    try:
        win = serve.run_window(eng, plan, trace_span, annotate)
        setup_s = win.w0 - T_PROCESS_START
        log(f"setup {setup_s:.1f}s = build {t_build:.1f} + compile/warm "
            f"{t_compile:.1f} + steady-state traffic {plan.warmup_s:.1f} "
            f"(+ imports); {len(win.steps)} steps")
        if win.compiles_in_window:
            print(f"benchmark: {win.compiles_in_window} compilation(s) "
                  f"inside the measured window", file=sys.stderr)
            return None, 3
        run = Run(cell, peaks, win, setup_s)
        run.trace = _reduce_trace(trace_dir, win.trace, "bench.engine_step",
                                  require_chip)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    # the engine's own admit spans, before the engine goes
    for tr in run.measured():
        for ph, kind, ts, _, _ in eng.tracer.events(tr.rid):
            if kind == "admit":
                tr.admit = ts
    measured = run.measured()
    failed = [tr for tr in measured
              if not tr.done or tr.handle.truncated or not tr.token_times]
    device["memory_peak_bytes"] = memory_peak(cell.chips)
    samples = [(tr.req.prompt, list(tr.handle.output_ids))
               for tr in correct.pick_sample(
                   win.tracks, seed, int(deploy["correct"]["sample"]))]
    # free the program's state before the reference takes the chip
    for tr in win.tracks:
        tr.handle = _Done(tr.handle)
    del eng, model
    gc.collect()
    t = time.perf_counter()
    checks, read = correct.judge(cell, seed, samples,
                                 sum(not tr.done for tr in measured))
    log(f"reference: {time.perf_counter() - t:.1f}s over "
        f"{read.get('positions')} positions")
    ok = all(v <= lim for v, lim in checks.values())
    result = {"correct": bool(ok), "attempted": len(measured),
              "failed": len(failed)}
    result["metrics"] = read_metrics(run, trace)
    _add_trace(result, device, run)
    if run.trace is not None:
        result["profiler_stalls_s"] = win.trace["profiler_stalls_s"]
    result["device"] = device
    result["setup_split_s"] = {"build": t_build, "compile_warm": t_compile,
                               "steady_traffic": plan.warmup_s}
    result["kv_pool"] = _kv_pool(run, deploy["engine"])
    result["reference"] = read
    result["compared"] = checks
    return result, 0


def _kv_pool(run: Run, engine: dict) -> dict:
    """How much of the KV pool the window's traffic held: the tokens in
    the cache of the requests each step carried, over the pool's."""
    pool = int(engine["num_blocks"]) * int(engine["block_size"])
    live = [sum(kv for _, kv in s.spans) for s in run.steps_in()]
    return {"tokens": pool,
            "live_mean_share": float(np.mean(live)) / pool if live else None,
            "live_peak_share": max(live) / pool if live else None}


class _Done:
    """What a finished request keeps once the engine is freed."""

    def __init__(self, handle):
        self.state = getattr(handle, "state", None)
        self.truncated = getattr(handle, "truncated", False)
        self.output_ids = list(getattr(handle, "output_ids", []))


def read_metrics(run: Run, traced: bool) -> dict:
    entries = run.cell.per_layer() if traced else run.cell.end_to_end()
    out = {}
    for m in entries:
        value = spec.metric_reader(m["name"], run.cell.dir)(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = ROOT, require_chip: bool = True):
    return run_serve(spec.Cell(workload, root), seed, seconds, trace,
                     require_chip)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result, rc = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    if result is None:
        return rc
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name} = {value!r} limit {limit!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
