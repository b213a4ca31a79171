#!/usr/bin/env python3
"""Finds the highest arrival rate an open-loop cell sustains: once, when
the cell is defined (the rate then stands in the cell's file as a number).

    python3 benchmark/tools/sweep_rate.py --workload <cell> --rates 1,2,3 --seconds 20

One process: the model and engine are built once and each rate gets a
window of its own.  A rate is sustained where requests due in the second
half of the window wait no longer for their first token than those of the
first half; past the knee the queue, and with it that wait, grows all
through the window.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np                               # noqa: E402

from harness import program, serve, spec, traffic   # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--engine", default="{}",
                    help="JSON overrides of the cell's engine settings")
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep_rate: needs a TPU", file=sys.stderr)
        return 2
    program.enable_compile_cache()
    cell = spec.Cell(args.workload)
    cfg = cell.config
    model = program.build_model(cell, args.seed)
    eng = program.build_engine(
        model, dict(cell.deploy["engine"], **json.loads(args.engine)))
    serve.warm_budgets(eng)
    for rate in (float(r) for r in args.rates.split(",")):
        plan = traffic.make_plan(cell.traffic, {"rate_per_s": rate},
                                 cfg["vocab_size"], args.seed, args.seconds)
        win = serve.run_window(eng, plan)
        m = [t for t in win.tracks if t.measured and t.token_times]
        mid = (win.w0 + win.w1) / 2
        ttft = lambda ts: [(t.token_times[0] - t.due) * 1e3 for t in ts]
        gaps = [(b - a) * 1e3 for t in m
                for a, b in zip(t.token_times, t.token_times[1:])]
        steps = [s for s in win.steps if win.w0 <= s.t1 < win.w1]
        pct = lambda v, q: float(np.percentile(v, q)) if len(v) else None
        print(json.dumps({
            "rate_per_s": rate, "requests": len(m),
            "ttft_p50_first_half_ms": pct(ttft([t for t in m if t.due < mid]), 50),
            "ttft_p50_second_half_ms": pct(ttft([t for t in m if t.due >= mid]), 50),
            "ttft_p90_ms": pct(ttft(m), 90),
            "itl_p50_ms": pct(gaps, 50), "itl_p95_ms": pct(gaps, 95),
            "step_ms_p50": pct([(s.t1 - s.t0) * 1e3 for s in steps], 50),
            "mean_running_slots": float(np.mean([s.running for s in steps])),
            "tokens_per_s": sum(s.decode_tokens + s.prefill_tokens
                                for s in steps) / plan.seconds,
            "drained": win.drained,
            "compiles_in_window": win.compiles_in_window}), flush=True)
        eng.finished.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
