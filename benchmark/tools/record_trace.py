#!/usr/bin/env python3
"""Records a SMALL device trace of a serving cell's real engine: a few
steps (decode-only and with a prefill chunk) under the profiler, with the
benchmark's own host span round each.  The file is kept (the benchmark's
runs delete theirs): it is what ``tests/test_xtrace.py`` reads.

    python3 benchmark/tools/record_trace.py --workload <cell> --out chiprun_out/trace
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np                                   # noqa: E402

from harness import program, serve, spec, xtrace    # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    program.enable_compile_cache()
    cell = spec.Cell(args.workload)
    cfg = cell.config
    model = program.build_model(cell, 0)
    eng = program.build_engine(model, cell.deploy["engine"])
    serve.warm_budgets(eng)
    rng = np.random.default_rng(0)
    for n in (40, 90, 200, 350):
        eng.add_request(rng.integers(1, cfg["vocab_size"], n), 32)
    for _ in range(3):
        eng.step()
    # a prompt that arrives now: the traced steps carry its chunks
    eng.add_request(rng.integers(1, cfg["vocab_size"], 700), 8)
    shutil.rmtree(args.out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(args.out, profiler_options=opts)
    spans = []
    for _ in range(args.steps):
        before = {r.req_id: (r.prefill_pos, len(r.output_ids))
                  for r in eng.slots if r is not None}
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            eng.step()
        spans.append(sorted(
            (r.prefill_pos - before.get(r.req_id, (0, 0))[0],
             len(r.prompt_ids) + len(r.output_ids))
            for r in eng.slots if r is not None))
    jax.profiler.stop_trace()
    path = xtrace.find_xplane(args.out)
    red = xtrace.reduce(xtrace.load(path))
    print(json.dumps({"file": path, "bytes": os.path.getsize(path),
                      "steps": args.steps, "busy_s": red["busy_s"],
                      "top_ops": xtrace.top_ops(red, 8),
                      "idle_by_owner": red["idle_by_owner"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
