"""Merged host + device Chrome traces.

The two-tier profiler (SURVEY §5.1) leaves three timeline sources lying
around: host ``RecordEvent`` spans (paddle_tpu.profiler), the runtime
span log this module keeps (step markers, checkpoint writes, comm
timeouts), and the device trace ``jax.profiler`` writes under its trace
dir — which on this jax build includes a ready-made chrome trace
(``plugins/profile/<run>/<host>.trace.json.gz``).  ``merge_chrome_trace``
folds all three into ONE chrome://tracing JSON so a single load shows
the train loop, the checkpoint writer and the XLA device activity
side by side.

Clock domains: host spans, the span log and caller groups share
``time.perf_counter``; the profiler's trace has a clock of its own.  Where
the span log holds ``serving.step`` records and the trace holds the
``engine.step`` events of the same steps, :func:`step_clock` measures the
offset between the two clocks and every perf_counter source is put on
the trace's timeline by it: a step record lands on its profiler span.
Where nothing joins them (a host-only run, a trace without serving
steps), the perf_counter sources start at t=0 together and the trace at
its own t=0, and how far the two timelines lie apart is not known.
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import statistics
import threading
import time
from typing import Dict, Iterable, List, Optional

__all__ = ["SpanLog", "span_log", "record_span", "record_instant",
           "load_device_trace_events", "merge_chrome_trace", "step_clock"]

_SPAN_LOG_CAP = 16384
# the serving step: its record in the span log
# (``inference.serving.STEP_SPAN``), its span in the profiler's trace, and
# the device line whose events are whole executions of its module
STEP_RECORD = "serving.step"
STEP_EVENT = "engine.step"
MODULES_LINE = "XLA Modules"
STEP_MODULE = "mixed_step"


class SpanLog:
    """Bounded in-memory log of runtime spans/instants (step markers,
    checkpoint writes, watchdog timeouts).  An append is one lock + one
    deque.append — cheap enough for per-step use; the cap drops the
    OLDEST entries so a week-long job keeps the recent window.  The
    append AND the eviction run under one lock: concurrent writers
    (train thread + checkpoint writer + watchdog) can never race the
    bound past ``maxlen`` or drop each other's fresh entries."""

    def __init__(self, maxlen: int = _SPAN_LOG_CAP):
        self._maxlen = max(1, int(maxlen))
        self._events: "collections.deque" = collections.deque()
        self._lock = threading.Lock()

    def _append(self, entry: tuple):
        with self._lock:
            self._events.append(entry)
            while len(self._events) > self._maxlen:
                self._events.popleft()

    def record(self, name: str, start: float, end: float,
               cat: str = "runtime", **args):
        """A completed span; start/end are time.perf_counter seconds."""
        self._append(("X", name, cat, start, end, args,
                      threading.get_ident()))

    def instant(self, name: str, ts: Optional[float] = None,
                cat: str = "runtime", **args):
        t = time.perf_counter() if ts is None else ts
        self._append(("i", name, cat, t, t, args,
                      threading.get_ident()))

    def events(self) -> List[tuple]:
        with self._lock:
            return list(self._events)

    def clear(self):
        with self._lock:
            self._events.clear()

    def __len__(self):
        with self._lock:
            return len(self._events)


# process-wide log every wired subsystem appends to
span_log = SpanLog()


def record_span(name: str, start: float, end: float,
                cat: str = "runtime", **args):
    span_log.record(name, start, end, cat, **args)


def record_instant(name: str, ts: Optional[float] = None,
                   cat: str = "runtime", **args):
    span_log.instant(name, ts, cat, **args)


def _tid_map(idents: Iterable[int]) -> Dict[int, int]:
    """Stable small thread ids (chrome renders 15-digit pthread idents
    as separate unreadable lanes)."""
    return {ident: i for i, ident in enumerate(sorted(set(idents)))}


def load_device_trace_events(trace_dir: str) -> List[dict]:
    """traceEvents from the chrome trace(s) jax.profiler wrote under
    ``trace_dir`` (``**/*.trace.json[.gz]``); [] when the dir is missing
    or holds no trace — a device-less CPU/host-only run merges cleanly
    to host spans alone."""
    if not trace_dir or not os.path.isdir(trace_dir):
        return []
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                  recursive=True)
        + glob.glob(os.path.join(trace_dir, "**", "*.trace.json"),
                    recursive=True))
    events: List[dict] = []
    for path in paths:
        try:
            if path.endswith(".gz"):
                with gzip.open(path, "rt") as f:
                    data = json.load(f)
            else:
                with open(path) as f:
                    data = json.load(f)
        except (OSError, ValueError):
            continue
        evs = data.get("traceEvents", data) if isinstance(data, dict) \
            else data
        if isinstance(evs, list):
            events.extend(e for e in evs if isinstance(e, dict))
    return events


def _host_events_json(host_events, pid: int,
                      t0: Optional[float] = None) -> List[dict]:
    """paddle_tpu.profiler _HostEvent list -> chrome 'X' events +
    name metadata, normalized to t=0 at ``t0`` (defaults to the
    earliest span)."""
    if not host_events:
        return []
    if t0 is None:
        t0 = min(e.start for e in host_events)
    tids = _tid_map(e.tid for e in host_events)
    # spans FIRST, name metadata after: tools that peek at
    # traceEvents[0] (and the repo's own round-trip checks) see a real
    # 'X' span, and chrome accepts metadata at any position
    out = [{"name": e.name, "ph": "X", "pid": pid,
            "tid": tids[e.tid], "ts": (e.start - t0) * 1e6,
            "dur": (e.end - e.start) * 1e6,
            "cat": getattr(e, "event_type", "UserDefined")}
           for e in sorted(host_events, key=lambda e: e.start)]
    out.append({"name": "process_name", "ph": "M", "pid": pid,
                "args": {"name": "host (RecordEvent)"}})
    for ident, tid in tids.items():
        out.append({"name": "thread_name", "ph": "M", "pid": pid,
                    "tid": tid, "args": {"name": f"host-thread-{tid}"}})
    return out


def _span_log_events_json(entries, pid: int,
                          t0: Optional[float] = None) -> List[dict]:
    if not entries:
        return []
    if t0 is None:
        t0 = min(e[3] for e in entries)
    tids = _tid_map(e[6] for e in entries)
    out = []
    for ph, name, cat, start, end, args, ident in entries:
        ev = {"name": name, "ph": ph, "pid": pid, "tid": tids[ident],
              "ts": (start - t0) * 1e6, "cat": cat}
        if ph == "X":
            ev["dur"] = (end - start) * 1e6
        else:
            ev["s"] = "t"          # thread-scoped instant
        if args:
            ev["args"] = {k: v for k, v in args.items()}
        out.append(ev)
    out.append({"name": "process_name", "ph": "M", "pid": pid,
                "args": {"name": "runtime (steps/ckpt/comm)"}})
    for ident, tid in tids.items():
        out.append({"name": "thread_name", "ph": "M", "pid": pid,
                    "tid": tid,
                    "args": {"name": f"runtime-thread-{tid}"}})
    return out


def _device_events_json(events: List[dict], pid_base: int,
                        ts0: Optional[float] = None) -> List[dict]:
    """Re-base the device trace: pids offset so they never collide with
    the host groups, timestamps shifted by ``ts0`` (µs; by default the
    earliest event's, which puts it at t=0)."""
    if not events:
        return []
    if ts0 is None:
        ts_vals = []
        for e in events:
            try:
                ts_vals.append(float(e["ts"]))
            except (KeyError, TypeError, ValueError):
                pass
        ts0 = min(ts_vals) if ts_vals else 0.0  # metadata-only: keep
    out = []
    for e in events:
        ev = dict(e)
        if "pid" in ev:
            try:
                ev["pid"] = pid_base + int(ev["pid"])
            except (TypeError, ValueError):
                ev["pid"] = pid_base
        else:
            ev["pid"] = pid_base
        try:
            ev["ts"] = float(ev["ts"]) - ts0
        except (KeyError, TypeError, ValueError):
            pass                             # no/odd ts: pass through
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            args = dict(ev.get("args") or {})
            args["name"] = f"device: {args.get('name', 'jax')}"
            ev["args"] = args
        out.append(ev)
    return out


def _extra_group_json(name: str, group_events: List[dict], pid: int,
                      t0: Optional[float]) -> List[dict]:
    """One caller-built track group (e.g. a request tracer's events):
    chrome dicts whose ``ts``/``dur`` are ABSOLUTE perf_counter seconds
    — this shifts them onto the shared t0 and scales to µs, assigns the
    group's pid, and appends its process_name metadata."""
    if not group_events:
        return []
    base = t0 or 0.0
    out = []
    for e in group_events:
        ev = dict(e)
        ev["pid"] = pid
        if "ts" in ev:
            ev["ts"] = (float(ev["ts"]) - base) * 1e6
        if "dur" in ev:
            ev["dur"] = float(ev["dur"]) * 1e6
        out.append(ev)
    out.append({"name": "process_name", "ph": "M", "pid": pid,
                "args": {"name": name}})
    return out


def _step_records(records) -> List[dict]:
    """``serving.step`` records as dicts of their fields with ``t0`` and
    ``t_end`` beside them; span-log entries are converted, dicts kept."""
    out = []
    for r in records:
        if isinstance(r, dict):
            out.append(r)
        elif r[1] == STEP_RECORD:
            out.append(dict(r[5], t0=r[3], t_end=r[4]))
    return out


def _device_modules(trace_events: List[dict]) -> List[tuple]:
    """``(start, end)`` seconds of every execution of the serving step's
    module on the trace's first device, in order; none without a device
    plane (the CPU)."""
    procs, lines = {}, {}
    for e in trace_events:
        if e.get("ph") != "M":
            continue
        name = (e.get("args") or {}).get("name", "")
        if e.get("name") == "process_name":
            procs[e.get("pid")] = name
        elif e.get("name") == "thread_name":
            lines[(e.get("pid"), e.get("tid"))] = name
    devices = sorted((name, pid) for pid, name in procs.items()
                     if str(name).startswith("/device:"))
    if not devices:
        return []
    pid = devices[0][1]
    return sorted(
        (float(e["ts"]) * 1e-6, (float(e["ts"]) + float(e["dur"])) * 1e-6)
        for e in trace_events
        if e.get("ph") == "X" and e.get("pid") == pid
        and lines.get((pid, e.get("tid"))) == MODULES_LINE
        and STEP_MODULE in str(e.get("name", "")))


def step_clock(trace_events: List[dict], records) -> Optional[dict]:
    """One clock for the step records, the profiler's host spans and the
    device plane.

    ``trace_events``: the profiler's chrome trace events
    (:func:`load_device_trace_events`); ``records``: ``serving.step``
    records, as span-log entries or as dicts with ``t0`` / ``t_end``.
    Each record is paired with the ``engine.step`` event of the same
    engine and ``step_num``.  Returns ``None`` where none pairs, else:

    - ``offset_s``: trace time minus ``perf_counter``, the median over
      the paired steps (a record's ``t0`` against its span's start), and
      ``offset_spread_s`` its third quartile less its first;
    - ``lead_s``: ``[lo, hi]``, the causal bounds of the lead L of the
      device plane's clock over the host's, with the host's instants put
      on the trace's clock by ``offset_s``.  A step's module cannot end
      on the device after its tokens reached the host, so L >= max over
      steps of (module end - ``t_tokens``); it cannot start before its
      launch call began, so L <= min over steps of (module start -
      ``t_launch``).  ``lead_width_s`` is hi - lo.  A booked step's
      launch call is the ``t_launch`` of the record its ``launch_step``
      names (the record itself where it books its own launch; the one
      before where a launch stays in flight across steps).  The booked
      steps whose launch call is in the trace pair in order with the
      executions of the step's module on the first device
      (``launches`` of them); one execution more, the launch still in
      flight when the trace stopped, is left out.  Where the counts
      differ otherwise, or there is no device plane, the three are
      ``None``.
    """
    recs = {(r.get("engine"), r.get("step")): r
            for r in _step_records(records)}
    spans = {}
    for e in trace_events:
        if e.get("name") != STEP_EVENT or e.get("ph") != "X":
            continue
        args = e.get("args") or {}
        try:
            key = (int(args["engine"]), int(args["step_num"]))
        except (KeyError, TypeError, ValueError):
            continue
        spans.setdefault(key, []).append(float(e["ts"]) * 1e-6)
    # a step seen twice (two captures in one directory) pairs with neither
    paired = [(ts[0], recs[key]) for key, ts in spans.items()
              if key in recs and len(ts) == 1]
    if not paired:
        return None
    offsets = sorted(ts - r["t0"] for ts, r in paired)
    q = (statistics.quantiles(offsets, n=4) if len(offsets) > 1
         else [offsets[0]] * 3)
    out = {"steps": len(paired), "offset_s": statistics.median(offsets),
           "offset_spread_s": q[2] - q[0],
           "launches": 0, "lead_s": None, "lead_width_s": None}
    traced = {(r.get("engine"), r.get("step")): r for _, r in paired}
    booked = []                      # (launch call, tokens on the host)
    for r in sorted((r for _, r in paired if r.get("budget")),
                    key=lambda r: r["t_tokens"]):
        launcher = traced.get((r.get("engine"),
                               r.get("launch_step", r.get("step"))))
        if launcher is not None:
            booked.append((launcher["t_launch"], r["t_tokens"]))
    modules = _device_modules(trace_events)
    if booked and len(modules) in (len(booked), len(booked) + 1):
        off = out["offset_s"]
        lo = max(end - (t_tokens + off)
                 for (_, end), (_, t_tokens) in zip(modules, booked))
        hi = min(start - (t_launch + off)
                 for (start, _), (t_launch, _) in zip(modules, booked))
        out.update(launches=len(booked), lead_s=[lo, hi],
                   lead_width_s=hi - lo)
    return out


def _plain(value):
    """JSON for a numpy value in a span's args (a step record's
    ``spans`` is an int32 array)."""
    if hasattr(value, "tolist"):
        return value.tolist()
    raise TypeError(f"{type(value).__name__} is not JSON serializable")


def merge_chrome_trace(path: str, host_events=None,
                       runtime_events=None,
                       device_trace_dir: Optional[str] = None,
                       extra_groups=None) -> str:
    """Write one chrome://tracing JSON folding host RecordEvent spans,
    the runtime span log, and the device trace (if any) — the
    observability subsystem's single-timeline artifact.

    host_events: ``Profiler.events`` list (or None).
    runtime_events: a :class:`SpanLog` / its ``events()`` list; defaults
    to the process-wide :data:`span_log`.
    device_trace_dir: a ``jax.profiler`` trace dir; missing/empty dirs
    degrade to a host-only trace (the device-less CPU contract).  Where
    its ``engine.step`` events pair with ``serving.step`` records in
    ``runtime_events`` (:func:`step_clock`), every perf_counter source is
    put on the trace's clock; otherwise each clock starts at its own t=0.
    extra_groups: ``[(process_name, chrome_event_dicts)]`` — additional
    track groups (one pid each) whose ``ts``/``dur`` are ABSOLUTE
    perf_counter seconds on the same clock as the host/runtime spans;
    the fleet request tracer (``request_trace.fleet_trace``) feeds the
    router's and every engine's request lanes through this.

    Output ordering is DETERMINISTIC: non-metadata events sort by
    ``(ts, pid, tid, name)`` — two spans sharing a timestamp always
    serialize in the same order, so traces diff cleanly across runs —
    with metadata after (the first traceEvent stays a real span).
    """
    if runtime_events is None:
        runtime_events = span_log
    if isinstance(runtime_events, SpanLog):
        runtime_events = runtime_events.events()
    pid = os.getpid()
    host_events = list(host_events or [])
    runtime_events = list(runtime_events or [])
    extra_groups = [(str(n), list(evs or []))
                    for n, evs in (extra_groups or [])]
    device = load_device_trace_events(device_trace_dir)
    # host spans, runtime spans and extra groups share the perf_counter
    # clock: ONE t0 across all of them, or a checkpoint 45s into the
    # profile would render at t=0 next to the first host span.  Where the
    # serving steps join that clock to the trace's, t0 is the trace's own
    # zero and the trace keeps its timestamps
    clock = step_clock(device, runtime_events) if device else None
    if clock is not None:
        t0, ts0 = -clock["offset_s"], 0.0
    else:
        starts = [e.start for e in host_events] \
            + [e[3] for e in runtime_events]
        for _name, evs in extra_groups:
            starts += [float(e["ts"]) for e in evs if "ts" in e]
        t0, ts0 = (min(starts) if starts else None), None
    events: List[dict] = []
    events.extend(_host_events_json(host_events, pid, t0))
    events.extend(_span_log_events_json(runtime_events, pid + 1, t0))
    for i, (name, evs) in enumerate(extra_groups):
        events.extend(_extra_group_json(name, evs, pid + 2 + i, t0))
    events.extend(_device_events_json(device, 1_000_000, ts0))
    # deterministic serialization: spans by (ts, pid, tid, name) —
    # ties included — then metadata (tools that peek at traceEvents[0]
    # must still see a real span)
    spans = [e for e in events if e.get("ph") != "M"]
    meta = [e for e in events if e.get("ph") == "M"]

    def _num(v):
        # device traces may carry non-numeric ids: numbers sort
        # numerically, anything else sorts after them as text — the
        # key never raises and stays deterministic either way
        try:
            return (0, float(v), "")
        except (TypeError, ValueError):
            return (1, 0.0, str(v))

    def _order(e):
        try:
            ts = float(e.get("ts", 0.0))
        except (TypeError, ValueError):
            ts = 0.0
        return (ts, _num(e.get("pid", 0)), _num(e.get("tid", 0)),
                str(e.get("name", "")))

    spans.sort(key=_order)
    out = {"displayTimeUnit": "ms", "traceEvents": spans + meta}
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, default=_plain)
    return path
