"""Reduction of a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` alone.  A device plane is named
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed
HLO op (a Pallas kernel appears under the ``name=`` of its
``pallas_call``).  Busy time is the union of those events' intervals, so
nesting and overlap are not counted twice.  Host spans that the benchmark
writes (``jax.profiler.TraceAnnotation``) are on the host planes of the
same timeline, which is how an idle gap gets an owner.
"""
from __future__ import annotations

import glob
import os

import re

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _merge(intervals):
    """Union of [start, end) intervals -> sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


_SUFFIX = re.compile(r"\.\d+$")


def op_name(event_name: str) -> str:
    """The trace names an op by its whole HLO line, ``%fusion.12 = f32[..]
    fusion(...)``.  Keep the op's own name without the ``%`` and the
    instance number: ``fusion``, ``ragged_paged_attention``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def device_ops(profile):
    """``{plane name: [(op name, start_ns, end_ns)]}`` of each device."""
    planes = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            planes[plane.name] = [
                (op_name(ev.name), ev.start_ns,
                 ev.start_ns + ev.duration_ns)
                for ev in line.events]
    return planes


def host_spans(profile, name: str):
    """[start_ns, end_ns) of every host event called ``name``."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    return sorted(out)


def _leaves(ops):
    """Events that contain no other event: a ``while`` or a ``call``
    spans its body's ops, and only the body's own time is a kernel's."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    leaves = []
    for i, (name, s, e) in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt[1] < e and nxt[2] <= e and nxt[1] >= s \
                and (nxt[1], nxt[2]) != (s, e):
            continue                      # a parent of the next event
        leaves.append((name, s, e))
    return leaves


def reduce(profile, step_span: str = "bench.engine_step") -> dict:
    """Busy seconds (mean over devices), seconds per op name (leaf events,
    mean over devices), and the idle gaps with what the host was in."""
    planes = device_ops(profile)
    if not planes:
        raise ValueError("the trace has no device plane with an "
                         f"{OPS_LINE!r} line")
    n = len(planes)
    busy = 0.0
    per_op = {}
    gaps = []
    steps = host_spans(profile, step_span)
    for ops in planes.values():
        merged = _merge([(s, e) for _, s, e in ops])
        busy += sum(e - s for s, e in merged) / 1e9
        for name, s, e in _leaves(ops):
            per_op[name] = per_op.get(name, 0.0) + (e - s) / 1e9 / n
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append((s1 - e0, e0, s1))
    gaps.sort(reverse=True)

    def owner(s, e):
        inside = sum(max(0, min(e, b) - max(s, a)) for a, b in steps)
        return ("inside engine.step()" if inside * 2 >= (e - s)
                else "between steps")

    by_owner = {}
    for g, s, e in gaps:
        who = owner(s, e)
        by_owner[who] = by_owner.get(who, 0.0) + g / 1e9
    return {
        "busy_s": busy / n,
        "devices": n,
        "op_seconds": per_op,
        "idle_by_owner": by_owner,
        "longest_gaps": [[owner(s, e), g / 1e9] for g, s, e in gaps[:10]],
    }


def kernel_seconds(reduction: dict, pattern: str) -> float:
    """Device seconds of the ops whose name contains ``pattern``."""
    return sum(t for name, t in reduction["op_seconds"].items()
               if pattern in name)


def top_ops(reduction: dict, n: int = 10):
    ops = sorted(reduction["op_seconds"].items(), key=lambda kv: -kv[1])
    return [[name, t] for name, t in ops[:n]]
