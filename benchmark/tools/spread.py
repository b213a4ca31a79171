#!/usr/bin/env python3
"""How widely a cell's runs spread, the way the bounds are set from it.

    python3 benchmark/tools/spread.py chiprun_out/sets/<cell>.A.*.out -- chiprun_out/sets/<cell>.B.*.out

Each file's last line is a result of ``run.py``; the two groups are the two
sets of runs on the same seeds.  For each metric: each set's median and
its spread (third minus first quartile of ``statistics.quantiles(n=4)``
over the median), the wider of the two, and five times that.
"""
from __future__ import annotations

import json
import statistics
import sys


def load(paths):
    out = []
    for p in paths:
        with open(p) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        out.append(json.loads(lines[-1]))
    return out


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    args = sys.argv[1:]
    cut = args.index("--")
    sets = [load(args[:cut]), load(args[cut + 1:])]
    names = sorted({m for s in sets for r in s for m in r["metrics"]})
    for r in (r for s in sets for r in s):
        if not r["correct"] or r["failed"]:
            print("NOT CORRECT or failed:", r["compared"], r["failed"])
    for name in names:
        vals = [[r["metrics"][name]["value"] for r in s
                 if name in r["metrics"]] for s in sets]
        med = [statistics.median(v) for v in vals]
        spr = [spread(v) for v in vals]
        print(f"{name}: medians {med[0]:.6g} / {med[1]:.6g} "
              f"(second over first {med[1] / med[0] - 1:+.2%}); spreads "
              f"{spr[0]:.2%} / {spr[1]:.2%}; five times the wider "
              f"{5 * max(spr):.2%}")
        print("   ", " ".join(f"{v:.6g}" for v in vals[0]), "|",
              " ".join(f"{v:.6g}" for v in vals[1]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
