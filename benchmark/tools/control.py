#!/usr/bin/env python3
"""A serving cell's control: the cell's own run, judged with the reference
in a lower precision put in the program's place, over the same prompts and
served tokens.  Never part of a benchmark run.

    python3 benchmark/tools/control.py --workload <cell> --seed <n> --seconds 20

``--precision int8`` (the precision below bfloat16) is the control and has
to come out ``"correct": false``.  ``--witness bf16`` also reads, over the
same samples, the reference in the precision the configuration states:
not a control, but what rounding alone does to a sparse model's routing.
Prints one JSON line: ``correct`` and ``compared`` are the control's, the
program's own readings of the same run are under ``reference``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run                                      # noqa: E402
from harness import correct                     # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--precision", choices=("int8", "bf16"), default="int8")
    ap.add_argument("--witness", choices=("bf16",))
    args = ap.parse_args()
    correct.IN_PROGRAMS_PLACE = args.precision
    witness = {}
    judge = correct.judge

    def judge_with_witness(cell, seed, samples, unfinished):
        if args.witness and samples:
            limits = cell.deploy["correct"]
            witness.update(correct.served_gap(
                cell, seed, samples,
                float(limits.get("router_margin_min", 0.0)), args.witness,
                limits.get("wide_gap")))
        return judge(cell, seed, samples, unfinished)

    correct.judge = judge_with_witness
    result, rc = run.run_cell(args.workload, args.seed, args.seconds, False)
    if result is None:
        return rc
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "correct": result["correct"], "compared": result["compared"],
        "reference": result["reference"], "witness": witness}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
