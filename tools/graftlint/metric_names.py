"""Metric-name lint — the former standalone ``tools/check_metric_names.py``
implementation, now a registered graftlint rule (``metric-names``); the
old CLI remains as a thin shim over this module.

Statically scans every registration site — ``counter("...")`` /
``gauge("...")`` / ``histogram("...")`` with a literal first argument —
under ``paddle_tpu/``, ``tools/`` and ``bench.py``, and enforces the
repo's metric-naming contract:

1. names are snake_case (``[a-z][a-z0-9_]*``);
2. counters end in ``_total``; gauges/histograms never do;
3. base units only: no ``_ms``/``_us``/``_mb``/``_kb``/... suffixes —
   durations are ``_seconds``, sizes are ``_bytes``;
4. the unit is the SUFFIX: a name containing ``seconds``/``bytes``
   anywhere else (before ``_total`` for counters) is malformed —
   except inside a trailing ``<unit>_per_<x>`` ratio (round 20:
   ``serving_hbm_bytes_per_token``), which is still a base unit;
5. one name, one type: the same name registered as two different kinds
   anywhere in the tree is an error (the runtime registry would also
   raise, but only when both sites actually execute);
6. required families + PACKAGE COVERAGE (tightened round 20): every
   contract metric (the set external dashboards/benches key on) must
   have at least one registration site INSIDE ``paddle_tpu/`` — a
   rename that silently drops one is an error here, not a dashboard
   surprise, and a bench/tools script re-registering the name no
   longer masks the serving code renaming it away;
7. label CARDINALITY (round 16): every label name used at a
   ``.labels(...)`` call site must be declared in ``LABEL_DOMAINS``
   with a finite value set (or the DYNAMIC sentinel for label values
   that are bounded by deployment shape, e.g. engine ids); literal
   values must be members of the declared set, and any value
   expression that smells of a per-request identifier (``req_id`` /
   ``rid`` / ``request_id`` / ``uuid``) is rejected outright — a
   per-request label value is an unbounded time-series leak, the one
   mistake a metrics registry cannot survive in production.

Pure stdlib + no jax import: safe to run anywhere.
"""
from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Tuple

from .core import Finding, Rule, register, repo_root

REPO = repo_root()

SCAN = ["paddle_tpu", "tools", "bench.py"]

# this package (rule implementations quote example registrations) and
# the shim never count as registration sites
_SKIP_PARTS = (os.path.join("tools", "graftlint"),
               os.path.join("tools", "check_metric_names.py"))

# .counter(" / counter(' / r.histogram(  ... with a literal first arg
# (possibly on the next line)
_REG_RE = re.compile(
    r"\b(counter|gauge|histogram)\(\s*[\"']([A-Za-z0-9_.\-]+)[\"']")

_SNAKE_RE = re.compile(r"^[a-z][a-z0-9_]*$")

_BANNED_SUFFIXES = ("_ms", "_msec", "_millis", "_us", "_micros", "_ns",
                    "_minutes", "_hours", "_kb", "_mb", "_gb", "_kib",
                    "_mib", "_gib")

# base-unit RATIOS: a unit may also sit inside a trailing
# '<unit>_per_<x>' compound (round 20: serving_hbm_bytes_per_token) —
# still a base unit, still machine-greppable
_PER_UNIT_RE = {u: re.compile(rf"{u}_per_[a-z0-9_]+$")
                for u in ("seconds", "bytes")}

# contract metrics external dashboards/benches key on: the serving
# engine must keep registering these names (see BENCH_SERVE_r10.json
# provenance; README "Observability" inventory)
REQUIRED_NAMES = frozenset({
    "serving_prefill_chunk_queue_depth",
    "serving_prefix_cache_lookups_total",
    "serving_prefix_cache_hit_tokens_total",
    "serving_prefix_cache_evictions_total",
    "serving_prefill_duration_seconds",
    "serving_ttft_seconds",
    # fused mixed prefill+decode step (round-11; BENCH_SERVE_r11.json)
    "serving_mixed_step_compiles_total",
    "serving_mixed_span_tokens_total",
    # tensor-parallel multichip serving (round-12; BENCH_SERVE_r12.json)
    "serving_tp_degree",
    "serving_tp_collective_bytes_total",
    # quantized serving (round-13; BENCH_QUANT_r13.json)
    "serving_kv_quant_dtype",
    "serving_quant_collective_bytes_total",
    "serving_quant_token_mismatch_total",
    # sampling + speculative decoding (round-14; BENCH_SPEC_r14.json)
    "serving_sampling_mode",
    "serving_spec_proposed_tokens_total",
    "serving_spec_accepted_tokens_total",
    "serving_spec_draft_step_duration_seconds",
    # multi-engine serving router (round-15; BENCH_ROUTER_r15.json)
    "router_requests_total",
    "router_prefix_route_hits_total",
    "router_requeues_total",
    "router_engine_healthy",
    "router_pending_depth",
    # request tracing + SLO attainment (round-16; BENCH_TRACE_r16.json)
    "router_slo_attained_total",
    "router_latency_quantile_seconds",
    "request_trace_spans_total",
    "request_trace_dropped_spans_total",
    # KV page migration + host-RAM prefix tier + disaggregated
    # serving (round-19; BENCH_DISAGG_r19.json)
    "serving_page_migrations_total",
    "serving_migrated_bytes_total",
    "serving_host_tier_hits_total",
    "serving_host_tier_restores_total",
    "serving_host_tier_spills_total",
    "router_role_dispatch_total",
    # fleet capacity & efficiency plane (round-20; BENCH_CAP_r20.json)
    "router_capacity_recommendation",
    "router_capacity_transitions_total",
    "router_capacity_saturation_ratio",
    "router_capacity_headroom_ratio",
    "router_capacity_tokens_per_second",
    "serving_step_mfu",
    "serving_hbm_bytes_per_token",
    "serving_model_flops_per_token",
    # 2D fsdp x tp mesh, train-to-serve (round-21; BENCH_SPMD_r21.json)
    "train_fsdp_degree",
    "serving_mesh_shape",
    "spmd_allgather_bytes_total",
    # context-parallel serving (round-22; BENCH_CP_r22.json)
    "serving_cp_degree",
    "serving_cp_collective_bytes_total",
    # multi-process serving fleet (round-23; BENCH_FLEET_r23.json)
    "router_rpc_requests_total",
    "router_rpc_retries_total",
    "router_rpc_latency_seconds",
    "fleet_engine_process_restarts_total",
    # expert-parallel MoE serving (round-24; BENCH_MOE_r24.json)
    "serving_ep_degree",
    "serving_moe_dispatch_tokens_total",
    "serving_ep_collective_bytes_total",
    # elastic actuation + live mesh reshape (round-25;
    # BENCH_ELASTIC_r25.json)
    "elastic_actions_total",
    "elastic_drained_requests_total",
    "elastic_warmup_restored_pages_total",
    "redistribute_bytes_total",
    "router_engine_pool_size",
})

# ---------------------------------------------------------------------------
# label-cardinality contract (round 16)
# ---------------------------------------------------------------------------
# sentinel: values are dynamic expressions but drawn from a set bounded
# by deployment shape (engine ids = the pool size), never per-request
DYNAMIC = object()

# the ONE declaration of every label name's finite value domain; a
# label name not in this table may not appear at any .labels() site
LABEL_DOMAINS = {
    "outcome": frozenset({"completed", "truncated", "rejected",
                          "hit", "miss",
                          "attained", "missed", "no_target",
                          # prefix-cache eviction outcomes (round 19)
                          "reclaimed", "skipped_pinned",
                          # fleet RPC outcomes (round 23)
                          "ok", "error"}),
    # fleet RPC methods (round 23): the closed wire-protocol verb set
    # (paddle_tpu.inference.fleet.RPC_METHODS)
    "method": frozenset({"hello", "add_request", "step",
                         "preempt_request", "extract_request",
                         "inject_request", "health_payload",
                         "ping", "shutdown"}),
    "reason": frozenset({"preempt", "engine_lost", "migrated",
                         # elastic pool retirement + the actuator's
                         # saturation-spread sweep (round 25)
                         "scale_down", "rebalance"}),
    "kind": frozenset({"decode", "prefill", "ttft", "tpot",
                       # redistribution traffic accounting (round 25):
                       # bytes that crossed chips vs the naive
                       # full-gather restore bill
                       "moved", "full_gather_equiv"}),
    "op": frozenset({"psum", "all_gather", "all_to_all"}),
    "q": frozenset({"p50", "p95", "p99"}),
    # page migration direction: out = extract (device→host), in =
    # inject (host→device)
    "direction": frozenset({"out", "in"}),
    # disaggregated-serving engine roles
    "role": frozenset({"prefill", "decode", "mixed"}),
    # MoE dispatch-token fates (round 24): the serving dispatch is
    # dropless, so 'dropped' exists to stay visibly zero; round 25
    # adds drain fates — how a scale_down victim's requests travelled
    "fate": frozenset({"routed", "dropped",
                       "migrated", "re_prefilled"}),
    # capacity-plane advisory actions (round 20)
    "action": frozenset({"scale_up", "scale_down", "rebalance",
                         "steady"}),
    # mesh axes (round 21, + cp round 22, + ep round 24):
    # serving_mesh_shape{axis}
    "axis": frozenset({"fsdp", "tp", "dp", "cp", "ep"}),
    # spmd param all-gather sites (round 21):
    # spmd_allgather_bytes_total{site}
    "site": frozenset({"train_params", "serving_params"}),
    "engine": DYNAMIC,              # engine ids: bounded by pool size
    "expert": DYNAMIC,              # index within the bank an engine
                                    # holds: bounded by the model config
    "metric": DYNAMIC,              # bench line names: bounded by the
                                    # bench's own mode set
    "unit": DYNAMIC,                # bench units: one per bench line
}

# expressions that smell of per-request identity: unbounded cardinality
_FORBIDDEN_VALUE_RE = re.compile(
    r"\breq_id\b|\brequest_id\b|\brid\b|\buuid\b|\breq\.req_id\b",
    re.IGNORECASE)

# .labels( ... ) with one nesting level of parens inside (str(...) etc.)
_LABELS_RE = re.compile(
    r"\.labels\(\s*([^()]*(?:\([^()]*\)[^()]*)*)\)", re.DOTALL)

_STR_LIT_RE = re.compile(r"""["']([^"']*)["']""")


def _split_kwargs(arglist: str):
    """Split a .labels(...) argument string on top-level commas,
    yielding (name, expr) pairs; tolerant of nested parens/quotes."""
    parts, depth, quote, cur = [], 0, None, []
    for ch in arglist:
        if quote:
            cur.append(ch)
            if ch == quote:
                quote = None
            continue
        if ch in "\"'":
            quote = ch
            cur.append(ch)
        elif ch in "([{":
            depth += 1
            cur.append(ch)
        elif ch in ")]}":
            depth -= 1
            cur.append(ch)
        elif ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    out = []
    for p in parts:
        if "=" not in p:
            continue                       # positional/odd: skip
        name, expr = p.split("=", 1)
        out.append((name.strip(), expr.strip()))
    return out


def _scan_files():
    for top in SCAN:
        path = os.path.join(REPO, top)
        if os.path.isfile(path):
            files = [path]
        else:
            files = []
            for root, _dirs, names in os.walk(path):
                files += [os.path.join(root, n) for n in names
                          if n.endswith(".py")]
        for fpath in sorted(files):
            rel = os.path.relpath(fpath, REPO)
            if any(part in rel for part in _SKIP_PARTS):
                continue
            try:
                with open(fpath, encoding="utf-8") as f:
                    yield rel, f.read()
            except OSError:
                continue


def find_label_sites():
    """[(relpath, lineno, label_name, value_expr)] for every kwarg of
    every ``.labels(...)`` call under the scan roots."""
    out = []
    for rel, text in _scan_files():
        for m in _LABELS_RE.finditer(text):
            line = text.count("\n", 0, m.start()) + 1
            for name, expr in _split_kwargs(m.group(1)):
                out.append((rel, line, name, expr))
    return out


def lint_label_sites(sites):
    """Violations of the label-cardinality contract (rule 7)."""
    errors = []
    for rel, line, name, expr in sites:
        where = f"{rel}:{line}"
        domain = LABEL_DOMAINS.get(name)
        if domain is None:
            errors.append(
                f"{where}: label {name!r} is not declared in "
                f"LABEL_DOMAINS — declare its finite value set (or "
                f"DYNAMIC with a boundedness argument)")
            continue
        if _FORBIDDEN_VALUE_RE.search(expr):
            errors.append(
                f"{where}: label {name!r} value {expr!r} is derived "
                f"from a per-request identifier — unbounded series "
                f"cardinality")
            continue
        if domain is DYNAMIC:
            continue
        literals = _STR_LIT_RE.findall(expr)
        for lit in literals:
            if lit not in domain:
                errors.append(
                    f"{where}: label {name!r} value {lit!r} is outside "
                    f"its declared domain {sorted(domain)}")
    return errors


def find_registrations() -> List[Tuple[str, int, str, str]]:
    """[(relpath, lineno, kind, name)] for every literal registration."""
    out = []
    for rel, text in _scan_files():
        for m in _REG_RE.finditer(text):
            kind, name = m.group(1), m.group(2)
            line = text.count("\n", 0, m.start()) + 1
            out.append((rel, line, kind, name))
    return out


def lint(regs) -> List[str]:
    errors = []

    def err(where, msg):
        errors.append(f"{where[0]}:{where[1]}: {msg}")

    kinds: Dict[str, Tuple[str, Tuple[str, int]]] = {}
    in_package: set = set()
    for rel, line, kind, name in regs:
        where = (rel, line)
        if not _SNAKE_RE.match(name):
            err(where, f"{name!r} is not snake_case")
            continue
        if kind == "counter" and not name.endswith("_total"):
            err(where, f"counter {name!r} must end in '_total'")
        if kind != "counter" and name.endswith("_total"):
            err(where, f"{kind} {name!r}: '_total' is reserved for "
                       f"counters")
        base = name[:-len("_total")] if name.endswith("_total") else name
        for suf in _BANNED_SUFFIXES:
            if base.endswith(suf):
                err(where, f"{name!r} uses a non-base unit {suf!r}; "
                           f"use '_seconds' / '_bytes'")
        for unit in ("seconds", "bytes"):
            if unit in base.split("_") and not base.endswith(unit) \
                    and not _PER_UNIT_RE[unit].search(base):
                err(where, f"{name!r}: unit '{unit}' must be the "
                           f"suffix (before '_total' for counters), "
                           f"or part of a trailing "
                           f"'{unit}_per_<x>' ratio")
        seen = kinds.get(name)
        if seen is None:
            kinds[name] = (kind, where)
        elif seen[0] != kind:
            err(where, f"{name!r} registered as {kind} here but as "
                       f"{seen[0]} at {seen[1][0]}:{seen[1][1]}")
        if rel.split(os.sep, 1)[0] == "paddle_tpu":
            in_package.add(name)
    # REQUIRED coverage (round 20): a contract name must have at least
    # one registration site INSIDE the package — a bench/tools script
    # re-registering the name must not mask the serving code renaming
    # it away (the dashboards scrape the serving process, not a bench)
    for name in sorted(REQUIRED_NAMES):
        if name not in kinds:
            errors.append(f"<scan>: required metric {name!r} is "
                          f"registered nowhere under {SCAN}")
        elif name not in in_package:
            errors.append(
                f"<scan>: required metric {name!r} has no registration "
                f"site inside paddle_tpu/ — only bench/tools sites "
                f"register it, so the serving contract is gone")
    return errors


def all_errors() -> List[str]:
    return lint(find_registrations()) + lint_label_sites(
        find_label_sites())


def registered_names() -> List[str]:
    return sorted({name for _, _, _, name in find_registrations()})


# ---------------------------------------------------------------------------
# CLI (preserved for the tools/check_metric_names.py shim)
# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    argv = list(sys.argv if argv is None else argv)
    regs = find_registrations()
    errors = lint(regs) + lint_label_sites(find_label_sites())
    uniq = sorted({name for _, _, _, name in regs})
    if errors:
        for e in errors:
            print(f"check_metric_names: {e}", file=sys.stderr)
        print(f"check_metric_names: FAILED — {len(errors)} violation(s) "
              f"across {len(regs)} registration sites", file=sys.stderr)
        return 1
    print(f"check_metric_names: OK — {len(regs)} registration sites, "
          f"{len(uniq)} metric names, 0 violations")
    if "--list" in argv:
        for name in uniq:
            print(f"  {name}")
    return 0


# ---------------------------------------------------------------------------
# graftlint rule
# ---------------------------------------------------------------------------
_LOC_RE = re.compile(r"^([^:]+):(\d+): (.*)$", re.DOTALL)


def _to_findings(errors: List[str]) -> List[Finding]:
    out = []
    for e in errors:
        m = _LOC_RE.match(e)
        if m:
            out.append(Finding("metric-names", m.group(1),
                               int(m.group(2)), m.group(3)))
        else:
            out.append(Finding("metric-names", "<scan>", 0,
                               e.replace("<scan>: ", "", 1)))
    return out


def _selftest() -> List[Finding]:
    # one injected defect per sub-contract: a camelCase gauge, a
    # per-request label value, and a required name whose only
    # registration site sits OUTSIDE the package (the round-20
    # coverage check) must all be caught.  Only the findings that name
    # the INJECTED defects count — the synthetic registration lists
    # also trip the other required-families errors, and counting that
    # collateral would let a blinded checker pass the selftest
    errs = lint([("inj.py", 1, "gauge", "badName")])
    errs += lint_label_sites([("inj.py", 2, "engine", "str(req.req_id)")])
    errs += lint([(os.path.join("tools", "inj_bench.py"), 1, "counter",
                   "router_requests_total")])
    hits = [e for e in errs
            if "is not snake_case" in e
            or "per-request identifier" in e
            or ("router_requests_total" in e
                and "no registration site inside paddle_tpu/" in e)]
    if len(hits) < 3:
        return []            # one of the three checkers went blind
    return _to_findings(hits)


register(Rule(
    id="metric-names",
    family="metrics",
    contract="metric names are snake_case, unit-suffixed base units, "
             "counters end _total, one name one type, required serving "
             "families present, label cardinality declared and bounded",
    check=lambda sources: _to_findings(all_errors()),
    selftest=_selftest,
))
