"""Scheduler: the fullest held expert's rows over the mean rows a held
expert was given (the step record's ``moe_rows_top`` over ``moe_rows`` /
experts held), summed over the window's launched steps: 1 where the
routing is even, and what a straggler expert costs where it is not."""
from harness import program_spans


def read(run):
    records = program_spans.launched(run)
    if not records or any("moe_rows_top" not in r for r in records):
        return None
    rows = sum(r["moe_rows"] for r in records)
    if not rows:
        return None
    return (sum(r["moe_rows_top"] for r in records)
            * run.config["n_routed_experts"] / rows)
