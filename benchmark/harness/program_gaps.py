"""Where the chip waits between launches, and what the start-up spent
building the step, read from the program's own step records.

A step record splits the launch: ``t_launch`` (operands
gathered, the jitted call made) sits between ``t_fill`` and
``t_dispatch`` (the call returned, work enqueued), and ``trace_s``,
``lower_s``, ``compile_s`` count the seconds the launch spent building
its module.  A program without those fields leaves every metric here out
of the line (``None``), as ``program_spans`` does for a program without
the record.
"""
from __future__ import annotations

from .program_spans import all_step_records, step_records
from .result import percentile


def host_gaps(records) -> list:
    """Seconds from one step's tokens on the host to the next step's
    launch enqueued, ``t_dispatch[N+1] - t_tokens[N]``, over consecutive
    steps of one engine that both launched: the bookkeeping, the
    caller's loop, admission, packing, filling, gathering the operands
    and the jitted call."""
    return [b["t_dispatch"] - a["t_tokens"]
            for a, b in zip(records, records[1:])
            if b["engine"] == a["engine"] and b["step"] == a["step"] + 1
            and a["budget"] and b["budget"]]


def _have(records, field: str) -> bool:
    """Whether the program writes ``field`` into its records."""
    return bool(records) and field in records[0]


def host_gap_ms(run):
    """Median host gap over the window's steps, milliseconds."""
    records = step_records(run)
    if not _have(records, "t_launch"):
        return None
    return percentile((g * 1e3 for g in host_gaps(records)), 50)


def runtime_gap_ms(run):
    """Over the traced seconds: the device's idle time less the host gaps
    of the launched pairs recorded there, per pair, milliseconds.  What
    no host phase fills: the fetch's wake-up after the device finished
    and the device's start after the launch was enqueued.  Durations
    alone, so no clock is shared; the idle before the traced part's
    first launch and after its last tokens is in it too."""
    if run.trace is None:
        return None
    records = all_step_records()
    if not _have(records, "t_launch"):
        return None
    tr = run.window.trace
    gaps = host_gaps([r for r in records
                      if tr["t0"] <= r["t0"] and r["t_end"] <= tr["t1"]])
    if not gaps:
        return None
    idle = run.trace["window_s"] - run.trace["busy_s"]
    return (idle - sum(gaps)) / len(gaps) * 1e3


def step_build_s(run):
    """Seconds the start-up spent tracing, lowering and compiling (or
    loading) the step's modules: the build fields of every record of
    the window's engine that ended before the window opened."""
    window = step_records(run)
    if not _have(window, "trace_s"):
        return None
    engine = window[0]["engine"]
    mine = [r for r in all_step_records() if r["engine"] == engine]
    if mine[0]["step"] != 0:
        raise RuntimeError(
            f"the program's span log no longer holds engine {engine}'s "
            f"first steps (oldest step {mine[0]['step']})")
    return sum(r["trace_s"] + r["lower_s"] + r["compile_s"]
               for r in mine if r["t_end"] < run.window.w0)
