"""Static VMEM-budget check for the hand-written Pallas kernels — the
former standalone ``tools/check_vmem_budget.py`` implementation, now a
registered graftlint rule (``vmem-budget``); the old CLI remains as a
thin shim over this module.

Every kernel's worst-case per-core VMEM footprint is computed from its
TILE SHAPES (``ops/pallas_kernels.kernel_vmem_report``: q tile
+ 2× double-buffered page DMA buffers + online-softmax
accumulators + score tiles, lane/sublane-padded the way Mosaic pads
them) at the declared serving/training envelope, and gated against the
per-core budget below.  A tile-size edit — a wider span window, a
bigger flash block, a third DMA slot — that blows the budget fails HERE
with one line per violation instead of as a Mosaic allocation error on
the first TPU run.

Budgets: the bench hardware (TPU v5e) has 128 MiB of VMEM per core;
the compiler needs headroom for spills and its own operand pipelining,
so each kernel is capped at HALF the core (64 MiB) and the serving
attention kernels — which must coexist with the fused step's other
fusions — at an eighth (16 MiB, the classic per-core figure older
generations actually have).  The grouped expert product holds whole-K
weight blocks and is a v5e-class kernel like the training ones.
"""
from __future__ import annotations

import sys
from typing import List

from .core import Finding, Rule, register, repo_root

MIB = 1 << 20

# per-core VMEM of the bench target (v5e); older parts have 16 MiB
VMEM_PER_CORE = 128 * MIB

# kernel family -> declared cap.  The serving kernels get the
# conservative 16 MiB cap (they must also run on 16 MiB parts and
# coexist with the fused serving step); the training flash kernels are
# v5e-class and get half a core.
BUDGETS = {
    "ragged_paged_fp32": 16 * MIB,
    "ragged_paged_int8": 16 * MIB,
    "ragged_paged_one_group": 16 * MIB,
    "paged_decode_fp32": 16 * MIB,
    "paged_decode_int8": 16 * MIB,
    "rope_qkv_epilogue": 16 * MIB,
    "ragged_latent_bf16": 16 * MIB,
    # whole-K weight blocks of an expert bank, two pipeline slots: a
    # v5e-class kernel (vmem_limit_bytes raised past Mosaic's default)
    "grouped_expert_wide": 64 * MIB,
    "grouped_expert_thin": 64 * MIB,
    # a span's whole float32 state block, in and out, two pipeline slots
    # each (vmem_limit_bytes raised past Mosaic's default)
    "ragged_gated_delta": 64 * MIB,
    "flash_fwd": 64 * MIB,
    "flash_bwd_fused": 64 * MIB,
}


def check(report=None):
    """[(kernel, bytes, budget, ok)] rows + [violation strings]."""
    if report is None:
        root = repo_root()
        if root not in sys.path:
            sys.path.insert(0, root)
        from paddle_tpu.ops.pallas_kernels import kernel_vmem_report
        report = kernel_vmem_report()
    rows, errors = [], []
    for name in sorted(report):
        used = int(report[name])
        budget = BUDGETS.get(name)
        if budget is None:
            errors.append(
                "%s: kernel family has no declared budget — add it to "
                "tools/graftlint/vmem.py BUDGETS "
                "(tools/check_vmem_budget.py is a shim)" % name)
            continue
        ok = used <= budget
        rows.append((name, used, budget, ok))
        if not ok:
            errors.append(
                "%s: worst-case VMEM %.2f MiB exceeds the declared "
                "%.0f MiB budget — shrink the tile (or, for a new "
                "hardware target, raise the budget with a comment)"
                % (name, used / MIB, budget / MIB))
    for name in sorted(set(BUDGETS) - set(report)):
        errors.append(
            "%s: declared budget has no kernel in kernel_vmem_report — "
            "remove it or fix the report" % name)
    return rows, errors


# ---------------------------------------------------------------------------
# CLI (preserved for the tools/check_vmem_budget.py shim)
# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    argv = list(sys.argv if argv is None else argv)
    rows, errors = check()
    if errors:
        for e in errors:
            print(f"check_vmem_budget: {e}", file=sys.stderr)
        print(f"check_vmem_budget: FAILED — {len(errors)} violation(s)",
              file=sys.stderr)
        return 1
    worst = max(rows, key=lambda r: r[1] / r[2])
    print("check_vmem_budget: OK — %d kernels within budget, 0 "
          "violations (worst: %s at %.2f/%.0f MiB)"
          % (len(rows), worst[0], worst[1] / MIB, worst[2] / MIB))
    if "--list" in argv:
        for name, used, budget, _ok in rows:
            print("  %-20s %8.2f MiB / %3.0f MiB"
                  % (name, used / MIB, budget / MIB))
    return 0


# ---------------------------------------------------------------------------
# graftlint rule
# ---------------------------------------------------------------------------
def _to_findings(errors: List[str]) -> List[Finding]:
    return [Finding("vmem-budget", "paddle_tpu/ops/pallas_kernels.py",
                    0, e) for e in errors]


def _selftest() -> List[Finding]:
    # injected defect: a kernel claiming 10× its declared budget.  Only
    # the over-budget finding counts — the one-kernel synthetic report
    # also trips the budget-without-kernel check, and counting that
    # collateral would let a blinded used<=budget comparison pass
    _rows, errors = check(report={"flash_fwd": 640 * MIB})
    return _to_findings([e for e in errors
                         if "exceeds the declared" in e])


register(Rule(
    id="vmem-budget",
    family="vmem",
    contract="every Pallas kernel family's worst-case tile VMEM "
             "footprint (from kernel_vmem_report) fits its declared "
             "per-core budget; every budget maps to a live kernel",
    check=lambda sources: _to_findings(check()[1]),
    selftest=_selftest,
    slow=True,      # imports paddle_tpu/jax for the live tile report
))
