"""Process-wide seconds spent building XLA programs, by phase.

JAX reports every trace to a jaxpr, every lowering to an MLIR module and
every backend compile through ``jax.monitoring``; the compile's report
holds the load from the persistent compilation cache where that hits (a
warm start loads what a first start compiles), so the cache's own report
of its load is not added again.  ONE duration listener, registered when
this module is imported, adds them up, so a caller that reads
:func:`compile_seconds` before and after a call knows how long that call
spent building programs, and in which phase.  It fires only when
something is built: a call that finds its executable costs nothing here.
The totals are the process's because JAX's hooks are: a listener per
engine would stay registered after its engine went, and each would count
every other engine's builds.

Nested traces are counted once.  Tracing a jitted function traces the
jitted functions it calls (a Pallas kernel's wrapper, say), and JAX
reports the inner trace and then the outer one that holds it; a report
whose span holds earlier reports of the same event replaces them.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Dict

import jax.monitoring

__all__ = ["COMPILE_EVENTS", "compile_seconds"]

# event -> the phase it is summed into
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}

_lock = threading.Lock()
_totals: Dict[str, float] = {"trace": 0.0, "lower": 0.0, "compile": 0.0}
# per event, the newest outermost spans (start, end, seconds counted):
# what a later, enclosing report of the same event replaces
_recent: Dict[str, collections.deque] = {
    event: collections.deque(maxlen=4096) for event in COMPILE_EVENTS}


def _on_duration(event: str, duration: float, **_):
    phase = COMPILE_EVENTS.get(event)
    if phase is None:
        return
    end = time.perf_counter()
    start = end - duration
    with _lock:
        spans = _recent[event]
        held = 0.0
        while spans and spans[-1][0] >= start:
            held += spans.pop()[2]
        spans.append((start, end, duration))
        _totals[phase] += duration - held


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_seconds() -> Dict[str, float]:
    """``{"trace", "lower", "compile"}``: seconds this process has spent
    tracing jaxprs, lowering them to MLIR, and compiling or loading from
    the compilation cache, since this module was imported."""
    with _lock:
        return dict(_totals)
