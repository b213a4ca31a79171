"""Aggregate every ``BENCH_*.json`` bench artifact into ONE
machine-readable trajectory: ``BENCH_INDEX.json``.

Every round since r05 has written a per-feature artifact (see the
``provenance`` rules in BASELINE.md), but the HISTORY has only been
readable by grepping prose — there was no single file answering "what
was the headline number and did the gates pass, per round".  This tool
closes that: it scans the repo root for ``BENCH_*.json``, extracts the
headline metric/value, the gate verdicts and the provenance line from
each (tolerant of the two artifact generations: the sectioned
``{metric, value, passed, gates}`` artifacts of r06+ and the
schema-less r07-r09 dicts), and writes:

- ``artifacts``: one row per file — round, file, headline metric +
  value + unit, passed, per-gate booleans, gate notes, platform;
- ``trajectory``: headline ``{metric: [[round, value], ...]}`` across
  rounds, so a regression shows up as a series, not a diff of prose;
- ``summary``: artifact/pass counts + the newest round.

Run as a verify-skill step (and from the capacity bench): the index is
regenerated, never hand-edited.  Pure stdlib, no jax import.

Usage::

    python tools/bench_index.py [out_path]     # default BENCH_INDEX.json
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys

_ROUND_RE = re.compile(r"_r(\d+)\.json$")


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _headline(data: dict):
    """(metric, value, unit) from any artifact generation."""
    if isinstance(data.get("metric"), str):
        value = data.get("value")
        if value is None:
            # BENCH_ATTN_r05 predates the value key; its number sits
            # under its own ratio name
            value = data.get("ring_over_full_ratio")
        return (data["metric"], value, data.get("unit"))
    # r07-r09 schema-less artifacts: pick a stable, documented headline
    for key in ("stall_ratio_async_over_sync", "state_bytes_ratio_stage2",
                "overhead_frac_median"):
        if key in data:
            return (key, data[key], "ratio")
    return (None, None, None)


def _gates(data: dict):
    """(passed, {gate: bool}, notes) — tolerant across generations."""
    gates = data.get("gates")
    gates = dict(gates) if isinstance(gates, dict) else {}
    notes = data.get("gate_notes")
    if notes is None and isinstance(data.get("gate"), (int, float, str)):
        notes = [f"gate={data['gate']!r}"]
    passed = data.get("passed")
    if passed is None and "ok" in data:
        passed = bool(data.get("ok"))
    if passed is None and gates:
        passed = all(bool(v) for v in gates.values())
    return (bool(passed) if passed is not None else None, gates,
            notes or [])


def index_artifact(path: str) -> dict:
    name = os.path.basename(path)
    m = _ROUND_RE.search(name)
    row = {"file": name,
           "round": int(m.group(1)) if m else None}
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        row["error"] = repr(e)[:200]
        return row
    if not isinstance(data, dict):
        row["error"] = "artifact is not a JSON object"
        return row
    metric, value, unit = _headline(data)
    passed, gates, notes = _gates(data)
    row.update({
        "metric": metric, "value": value, "unit": unit,
        "passed": passed, "gates": gates, "gate_notes": notes,
        "platform": data.get("platform"),
        "provenance": data.get("provenance"),
    })
    return row


def build_index(root: str) -> dict:
    paths = sorted(glob.glob(os.path.join(root, "BENCH_*.json")))
    # the index must never fold ITSELF into the trajectory
    paths = [p for p in paths
             if os.path.basename(p) != "BENCH_INDEX.json"]
    rows = [index_artifact(p) for p in paths]
    rows.sort(key=lambda r: (r["round"] if r["round"] is not None
                             else -1, r["file"]))
    trajectory = {}
    for r in rows:
        if r.get("metric") is None or r.get("value") is None \
                or r["round"] is None:
            continue
        trajectory.setdefault(r["metric"], []).append(
            [r["round"], r["value"]])
    rounds = [r["round"] for r in rows if r["round"] is not None]
    return {
        "generated_by": "tools/bench_index.py",
        "artifacts": rows,
        "trajectory": trajectory,
        "summary": {
            "artifacts": len(rows),
            "passed": sum(1 for r in rows if r.get("passed") is True),
            "failed": sum(1 for r in rows if r.get("passed") is False),
            "unparsed": sum(1 for r in rows if "error" in r),
            "newest_round": max(rounds) if rounds else None,
        },
    }


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    root = repo_root()
    out_path = argv[0] if argv else os.path.join(root,
                                                 "BENCH_INDEX.json")
    index = build_index(root)
    if not index["artifacts"]:
        print("bench_index: no BENCH_*.json artifacts found under "
              f"{root}", file=sys.stderr)
        return 1
    tmp = f"{out_path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(index, f, indent=1, sort_keys=False)
    os.replace(tmp, out_path)
    s = index["summary"]
    for r in index["artifacts"]:
        mark = {True: "PASS", False: "FAIL", None: " ?  "}[r.get("passed")]
        print("  r%-3s %-24s %s  %s=%r"
              % (r["round"], r["file"], mark, r.get("metric"),
                 r.get("value")), file=sys.stderr)
    print(f"bench_index: {s['artifacts']} artifacts "
          f"({s['passed']} pass / {s['failed']} fail / "
          f"{s['unparsed']} unparsed), newest round "
          f"{s['newest_round']} -> {out_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
