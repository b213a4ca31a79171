"""graftlint — the unified static-analysis suite (round 18).

Tier-1 carries ONE smoke test (the full ``--ci`` rule set run
in-process against the repo — the satellite's ≤10s allowance; the
suite is otherwise AT its 870s budget).  Everything else — the
per-rule fixture sweep, the subprocess CLI/exit-code contract, the
self-test drill — runs in the slow lane.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")

if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)

import graftlint                                       # noqa: E402
from graftlint import concurrency, trace_safety        # noqa: E402
from graftlint.core import (SourceFile, apply_waivers,  # noqa: E402
                            iter_rules, run_rules,
                            waiver_hygiene_findings)


def _fixture(name: str) -> str:
    with open(os.path.join(FIXTURES, name), encoding="utf-8") as f:
        return f.read()


# ---------------------------------------------------------------------------
# tier-1 smoke: the repo itself is lint-clean under the full rule set
# ---------------------------------------------------------------------------
def test_lint_ci_clean_on_repo():
    """``tools/lint.py --ci`` semantics, in-process (the subprocess
    variant incl. cold jax import is the slow-lane test): every
    registered rule over the live tree, zero unwaived findings, zero
    internal errors.  Runs the compiled-artifact pass too — in-suite
    jax is already up, so the tiny 1-layer artifacts compile in ~3s."""
    findings, errors = run_rules()      # all rules, shared source scan
    assert errors == [], "\n".join(errors)
    live = [f.render() for f in findings if not f.waived]
    assert live == [], "\n".join(live)
    # the waivers that exist are all reasoned (hygiene rule is in the
    # run above, but assert the invariant directly too)
    for f in findings:
        if f.waived:
            assert f.waive_reason


def test_hlo_artifacts_hold_one_serving_step():
    """The serving artifacts are the mixed step's (and the migration
    inject): no module of a split decode or prefill step is lowered."""
    from graftlint import hlo
    names = set(hlo.build_artifacts())    # cached from the smoke above
    assert f"mixed_step@T{hlo.MIXED_T}" in names
    assert not [n for n in names
                if n.startswith(("decode_step@", "prefill_step@"))]


# ---------------------------------------------------------------------------
# slow lane: per-rule fixture sweep
# ---------------------------------------------------------------------------
_TRACE_RULES = ["trace_host_transfer", "trace_f64_literal",
                "trace_prngkey", "trace_shape_branch"]
_CONC_RULES = ["conc_unguarded_write", "conc_lock_order"]


@pytest.mark.slow
@pytest.mark.parametrize("stem", _TRACE_RULES)
def test_trace_rule_fixtures(stem):
    rule = stem.replace("_", "-")
    pos = trace_safety.findings_for_snippet(_fixture(f"{stem}_pos.py"))
    neg = trace_safety.findings_for_snippet(_fixture(f"{stem}_neg.py"))
    assert [f for f in pos if f.rule == rule], \
        f"{rule} missed its positive fixture"
    assert not [f for f in neg if f.rule == rule], \
        f"{rule} false-fired on its negative fixture: " \
        + "\n".join(f.render() for f in neg)


@pytest.mark.slow
@pytest.mark.parametrize("stem", _CONC_RULES)
def test_conc_rule_fixtures(stem):
    rule = stem.replace("conc_", "conc-").replace("_", "-")
    pos = concurrency.findings_for_snippet(_fixture(f"{stem}_pos.py"))
    neg = concurrency.findings_for_snippet(_fixture(f"{stem}_neg.py"))
    assert [f for f in pos if f.rule == rule], \
        f"{rule} missed its positive fixture"
    assert not [f for f in neg if f.rule == rule], \
        f"{rule} false-fired on its negative fixture: " \
        + "\n".join(f.render() for f in neg)


@pytest.mark.slow
def test_unguarded_fixture_details():
    """The positive fixture's two defects are both found (thread-side
    append and racing reset), and the guarded mutation is not."""
    found = concurrency.findings_for_snippet(
        _fixture("conc_unguarded_write_pos.py"))
    lines = {f.line for f in found if f.rule == "conc-unguarded-write"}
    text = _fixture("conc_unguarded_write_pos.py").splitlines()
    flagged = {text[ln - 1].strip() for ln in lines}
    assert any("timed_out.append" in s for s in flagged)
    assert any("self.inflight = {}" in s for s in flagged)
    assert not any("timed_out.clear" in s for s in flagged)


@pytest.mark.slow
def test_lock_order_fixture_details():
    """Cycle AND plain-Lock self-deadlock both surface; the RLock
    variant stays clean."""
    found = concurrency.findings_for_snippet(
        _fixture("conc_lock_order_pos.py"))
    msgs = [f.message for f in found if f.rule == "conc-lock-order"]
    assert any("cycle" in m for m in msgs)
    assert any("self-deadlock" in m for m in msgs)


@pytest.mark.slow
def test_waiver_fixtures():
    """Bare waivers are findings; a reasoned waiver both passes
    hygiene and actually suppresses its target finding."""
    pos = SourceFile("waiver_hygiene_pos.py",
                     _fixture("waiver_hygiene_pos.py"))
    bad = waiver_hygiene_findings([pos])
    assert len(bad) == 2                  # no-rule + no-reason
    assert any("names no rule" in f.message for f in bad)
    assert any("bare waiver" in f.message for f in bad)

    neg = SourceFile("waiver_hygiene_neg.py",
                     _fixture("waiver_hygiene_neg.py"))
    assert waiver_hygiene_findings([neg]) == []
    found = trace_safety.analyze_source(neg)
    prng = [f for f in found if f.rule == "trace-prngkey"]
    assert prng, "fixture must trip trace-prngkey pre-waiver"
    apply_waivers(found, [neg])
    assert all(f.waived and f.waive_reason for f in prng)


# ---------------------------------------------------------------------------
# slow lane: CLI contract (subprocess — exit codes, --json, --list,
# --selftest)
# ---------------------------------------------------------------------------
def _run_cli(*args, timeout=300):
    return subprocess.run(
        [sys.executable, os.path.join(TOOLS, "lint.py"), *args],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


@pytest.mark.slow
def test_cli_ci_clean_and_json():
    proc = _run_cli("--ci", "--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["ok"] is True
    assert doc["internal_errors"] == []
    assert set(doc["rules"]) == {r.id for r in iter_rules()}
    assert all(f["waived"] for f in doc["findings"])
    # the <60s CPU budget from the acceptance criteria, with margin
    assert doc["elapsed_s"] < 60


@pytest.mark.slow
def test_cli_list_is_the_generated_inventory():
    proc = _run_cli("--list")
    assert proc.returncode == 0, proc.stderr
    for r in iter_rules():
        assert r.id in proc.stdout        # BASELINE.md inventory source


@pytest.mark.slow
def test_cli_selftest_catches_injected_defects():
    """One injected defect per rule family, each caught (the
    acceptance-criteria drill: trace-safety, HLO contract, concurrency,
    metric-names, vmem)."""
    proc = _run_cli("--ci", "--selftest")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for rid in ("trace-host-transfer", "hlo-donation", "hlo-f64",
                "hlo-packed-layout", "conc-unguarded-write",
                "conc-lock-order", "metric-names", "vmem-budget"):
        assert f"selftest {rid}" in proc.stdout
    assert "BLIND" not in proc.stdout


@pytest.mark.slow
def test_exit_code_contract_findings():
    """Exit 1 with findings: run the fast families against a doctored
    tree (a copy of a positive fixture placed under a temp repo's
    scan root)."""
    import shutil
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        os.makedirs(os.path.join(td, "paddle_tpu"))
        shutil.copy(
            os.path.join(FIXTURES, "trace_prngkey_pos.py"),
            os.path.join(td, "paddle_tpu", "bad.py"))
        findings, errors = run_rules(
            ["trace-prngkey", "waiver-hygiene"], root=td)
        assert errors == []
        assert [f for f in findings if f.rule == "trace-prngkey"]
