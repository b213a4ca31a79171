#!/usr/bin/env python
"""graftlint runner — the repo's one static-analysis entry point.

Usage:
    python tools/lint.py                 # fast rules (pure AST, <1s)
    python tools/lint.py --ci            # everything, incl. compiled-
                                         # artifact contracts (~<60s)
    python tools/lint.py --list          # rule inventory + contracts
    python tools/lint.py --selftest      # inject one defect per rule,
                                         # assert each rule catches it
    python tools/lint.py --json          # machine-readable findings
    python tools/lint.py --only trace-safety,concurrency
    python tools/lint.py --ci --skip hlo-contracts

Exit codes (stable contract for CI/autoscaler consumption):
    0  clean (every finding fixed or reason-waived)
    1  findings
    2  internal error (a rule crashed, a self-test went blind)

Waivers: `# graftlint: waive[rule-id] -- reason` on the finding line or
the line above.  Reasonless waivers suppress nothing and are themselves
findings (waiver-hygiene).

Subsumes ``check_metric_names.py`` and ``check_vmem_budget.py`` — both
old CLIs remain as thin shims over the same registered rules.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
for p in (_HERE, _REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

# slow rules build jax artifacts; a lint run must not take the chip
# (one process per chip) unless the caller pins a platform itself
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import graftlint                                       # noqa: E402
from graftlint import core                             # noqa: E402


def _select(only: str, skip: str, ci: bool):
    known = {r.id for r in core.iter_rules()} \
        | {r.family for r in core.iter_rules()}
    for arg, val in (("--only", only), ("--skip", skip)):
        bad = {k.strip() for k in val.split(",") if k.strip()} - known
        if bad:
            # an unknown key silently skipping nothing (or failing to
            # skip what was meant) is a CI hazard — fail loudly as an
            # internal error (exit 2), never a green no-op
            print(f"lint.py: unknown {arg} key(s) {sorted(bad)}; run "
                  f"--list for rule ids/families", file=sys.stderr)
            raise SystemExit(2)
    rules = core.iter_rules()
    if not ci and not only:
        rules = [r for r in rules if not r.slow]
    if only:
        keys = {k.strip() for k in only.split(",") if k.strip()}
        rules = [r for r in core.iter_rules()
                 if r.id in keys or r.family in keys]
    if skip:
        keys = {k.strip() for k in skip.split(",") if k.strip()}
        rules = [r for r in rules
                 if r.id not in keys and r.family not in keys]
    return rules


def _cmd_list() -> int:
    print("graftlint rules (id · family · contract):")
    for r in core.iter_rules():
        lane = "slow" if r.slow else "fast"
        print(f"  {r.id:<22} [{r.family}/{lane}]")
        print(f"      {r.contract}")
    return 0


def _cmd_selftest(rules) -> int:
    """One injected defect per rule; a rule that fails to catch its own
    defect has gone blind — exit 2 (internal error), not 1."""
    blind, crashed = [], []
    for r in rules:
        try:
            found = r.selftest()
        except Exception as e:                         # noqa: BLE001
            import traceback
            crashed.append((r.id, e))
            traceback.print_exc()
            continue
        caught = [f for f in found if f.rule == r.id]
        if caught:
            print(f"selftest {r.id:<22} OK — injected defect caught "
                  f"({len(caught)} finding(s))")
        else:
            blind.append(r.id)
            print(f"selftest {r.id:<22} BLIND — injected defect NOT "
                  f"caught", file=sys.stderr)
    if crashed or blind:
        print(f"graftlint selftest: FAILED — {len(blind)} blind, "
              f"{len(crashed)} crashed", file=sys.stderr)
        return 2
    print(f"graftlint selftest: OK — {len(rules)} rules each caught "
          f"their injected defect")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="lint.py", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--ci", action="store_true",
                    help="run every rule incl. slow artifact contracts")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable output")
    ap.add_argument("--list", action="store_true", dest="do_list",
                    help="print the rule inventory")
    ap.add_argument("--selftest", action="store_true",
                    help="inject one defect per rule; assert caught")
    ap.add_argument("--only", default="",
                    help="comma list of rule ids / families to run")
    ap.add_argument("--skip", default="",
                    help="comma list of rule ids / families to skip")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="also print waived findings")
    args = ap.parse_args(argv)

    if args.do_list:
        return _cmd_list()
    if args.selftest:
        # the self-test covers EVERY registered rule by default (the
        # slow rules' injectors use doctored artifacts — no jax, no
        # cost); --only/--skip still narrow it explicitly
        return _cmd_selftest(_select(args.only, args.skip, ci=True))
    rules = _select(args.only, args.skip, args.ci)

    t0 = time.time()
    try:
        findings, errors = core.run_rules([r.id for r in rules])
    except Exception as e:                             # noqa: BLE001
        import traceback
        traceback.print_exc()
        print(f"graftlint: internal error: {e}", file=sys.stderr)
        return 2
    live = [f for f in findings if not f.waived]
    waived = [f for f in findings if f.waived]
    dt = time.time() - t0

    if args.as_json:
        print(json.dumps({
            "ok": not live and not errors,
            "rules": [r.id for r in rules],
            "findings": [f.as_json() for f in findings],
            "internal_errors": errors,
            "elapsed_s": round(dt, 3),
        }, indent=2))
        return 2 if errors else (1 if live else 0)

    if errors:
        for e in errors:
            print(f"graftlint: INTERNAL: {e}", file=sys.stderr)
        return 2
    for f in live:
        print(f"graftlint: {f.render()}", file=sys.stderr)
    if args.verbose:
        for f in waived:
            print(f"graftlint: {f.render()}")
    if live:
        print(f"graftlint: FAILED — {len(live)} finding(s) "
              f"({len(waived)} waived) across {len(rules)} rules "
              f"in {dt:.1f}s", file=sys.stderr)
        return 1
    print(f"graftlint: OK — 0 findings ({len(waived)} waived) across "
          f"{len(rules)} rules in {dt:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
