"""What the program recorded of its own serving steps, read for metrics.

The second and last module that imports the system under test:
``program.py`` builds it, this one reads what it wrote while it ran.  Since
PR 24 every ``ContinuousBatchingEngine.step()`` ends by writing one
``serving.step`` record into the process-wide
``paddle_tpu.observability.span_log`` (phase boundaries on
``time.perf_counter``, which is the benchmark's clock too; the budget
launched, the real tokens, the spans as packed).  The log outlives the
engine, which a run frees before it reads its metrics.

A program from before PR 24 has no such record: ``step_records`` then
returns ``None``, every reader below returns ``None`` and the metric is
left out of the line.  A program that has the record and a log that does
not pair with the benchmark's own steps is a fault, and raises.
"""
from __future__ import annotations

from .result import percentile


def _step_log():
    """``(span_log, record name)``, or ``None`` where the program writes
    no step record."""
    try:
        from paddle_tpu.inference import serving
        from paddle_tpu.observability import span_log
    except ImportError:
        return None
    name = getattr(serving, "STEP_SPAN", None)
    return None if name is None else (span_log, name)


def all_step_records():
    """Every step record still in the log, oldest first, as a dict of
    its fields with ``t0`` and ``t_end`` beside them; ``None`` where the
    program writes none."""
    found = _step_log()
    if found is None:
        return None
    log, name = found
    return [dict(ev[5], t0=ev[3], t_end=ev[4]) for ev in log.events()
            if ev[1] == name]


def step_records(run):
    """The records of the steps that ended in the window, after a check
    that they pair one to one with the benchmark's own ``run.steps_in()``:
    each record inside its ``StepRec``'s ``[t0, t1]``.  A window the log
    no longer covers (it keeps its newest 16,384 entries) or a count that
    differs raises: there is no partial answer."""
    records = all_step_records()
    if records is None:
        return None
    steps = run.steps_in()
    if not steps:
        return []
    lo, hi = steps[0].t0, steps[-1].t1
    mine = [r for r in records if lo <= r["t0"] and r["t_end"] <= hi]
    if len(mine) != len(steps):
        raise RuntimeError(
            f"the program's span log holds {len(mine)} step records for "
            f"the window's {len(steps)} steps (log of {len(records)} "
            f"records, oldest at {records[0]['t0'] if records else None}"
            f", window from {lo})")
    for rec, step in zip(mine, steps):
        if not (step.t0 <= rec["t0"] and rec["t_end"] <= step.t1):
            raise RuntimeError(
                f"step record {rec['step']} [{rec['t0']}, {rec['t_end']}]"
                f" lies outside its step [{step.t0}, {step.t1}]")
    return mine


def launched(run):
    """The window's records of steps that launched something."""
    records = step_records(run)
    if records is None:
        return None
    return [r for r in records if r["budget"] > 0]


def launch_ms(rec) -> float:
    """The whole of the launch: transfer of the pack, enqueue, the wait
    for the device, the token ids back."""
    return (rec["t_tokens"] - rec["t_fill"]) * 1e3


def engine_host_ms(run):
    """Median over launched steps of the step less its launch:
    admission, packing, filling and bookkeeping."""
    records = launched(run)
    if not records:
        return None
    return percentile(((r["t_end"] - r["t0"]) * 1e3 - launch_ms(r)
                       for r in records), 50)


def budget_fill(run):
    """100 x real tokens over padded budget, launched steps."""
    records = launched(run)
    if not records:
        return None
    return (100.0 * sum(r["tokens"] for r in records)
            / sum(r["budget"] for r in records))


def chunk_step_share(run):
    """Share of launched steps that carried prefill tokens, percent."""
    records = launched(run)
    if not records:
        return None
    return 100.0 * sum(r["n_pre"] > 0 for r in records) / len(records)


def step_launch_ms(run, chunk: bool):
    """Median launch over the steps with (``chunk``) or without prefill
    tokens."""
    records = launched(run)
    if records is None:
        return None
    return percentile((launch_ms(r) for r in records
                       if (r["n_pre"] > 0) == chunk), 50)
