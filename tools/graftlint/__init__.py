"""graftlint — the repo's unified static-analysis suite (round 18).

The reference Paddle enforces its IR invariants with a pass/lint
infrastructure; this repo's hardest-won invariants — budget-bounded
compiles, donated pool aliasing, the one-packed-host-transfer rule
(round 11), and lock discipline across the threaded host-control
modules — were enforced only by runtime tests.  graftlint checks them
statically, in seconds, the same move TPP (arXiv:2104.05755) makes for
kernels: declare the contract once, verify it mechanically everywhere
it is composed.

Three pass families plus the two pre-existing lints as registered
rules:

- **trace-safety** (AST): inside ``@jax.jit``/traced step bodies and
  Pallas kernels — host transfers on traced values, f64-staging
  literals (x64 is globally on for paddle parity), ``PRNGKey``
  construction, shape-dependent Python control flow.
- **hlo-contracts** (compiled artifacts): AOT-lower the train step and
  the serving step once and assert donation actually aliases
  the KV pools, no f64 op appears, and the packed-operand layout
  matches the pinned formula.
- **concurrency** (AST): per-class field-access maps over every
  lock-using host-plane module — attributes touched from
  thread/callback contexts must be written under the class's lock —
  plus lock-acquisition-order cycle detection.
- **metric-names** / **vmem-budget**: the former standalone
  ``tools/check_metric_names.py`` / ``tools/check_vmem_budget.py``
  (both CLIs remain as thin shims over these rules).

Findings are suppressible only via an inline reasoned waiver::

    # graftlint: waive[rule-id] -- why this is safe here

on the finding line or the line directly above it.  A waiver without a
reason is itself a finding (``waiver-hygiene``).  ``tools/lint.py`` is
the single runner (``--ci`` / ``--json`` / ``--list`` / ``--selftest``);
the self-test injects one known defect per rule and asserts the rule
catches it, so a refactor that silently blinds a pass fails loudly.
"""
from __future__ import annotations

from .core import (Finding, Rule, iter_rules, get_rule, register,
                   run_rules, repo_root)

__all__ = ["Finding", "Rule", "iter_rules", "get_rule", "register",
           "run_rules", "repo_root"]


def _load_all() -> None:
    """Import every rule module so the registry is complete (each
    module registers its rules at import time)."""
    from . import trace_safety    # noqa: F401
    from . import concurrency     # noqa: F401
    from . import metric_names    # noqa: F401
    from . import vmem            # noqa: F401
    from . import hlo             # noqa: F401


_load_all()
