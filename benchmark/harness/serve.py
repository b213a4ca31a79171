"""Drives a serving cell: warm-up, the measured window, the drain.

One thread offers the load and steps the engine, as a front end that owns
its engine does.  The engine is used through ``add_request`` / ``step`` /
``has_work`` and the request objects it hands back in ``waiting``: each
emitted token is stamped with the clock when the ``step()`` that produced
it returned.

Clock: ``time.perf_counter`` throughout, the engine's own spans' clock.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from .traffic import Plan, Request

DRAIN_LIMIT_S = 60.0


@dataclass
class Track:
    """One request as offered, and what came back."""
    req: Request
    due: float                       # absolute, perf_counter
    sent: float = 0.0
    handle: object = None            # the engine's GenerationRequest
    rid: int = -1
    token_times: List[float] = field(default_factory=list)
    seen_prefill: int = 0
    measured: bool = False           # due inside the window
    admit: Optional[float] = None    # the engine's admit span, if read

    @property
    def done(self):
        return self.handle is not None and self.handle.state == "done"


@dataclass
class StepRec:
    t0: float
    t1: float
    decode_tokens: int
    prefill_tokens: int
    spans: list                      # [(q_len, kv_len)] as packed
    running: int                     # occupied slots after the step


@dataclass
class WindowResult:
    t_start: float                   # warm-up traffic begins
    w0: float                        # window opens
    w1: float                        # window closes
    tracks: List[Track]
    steps: List[StepRec]
    compiles_in_window: int
    drained: bool
    trace: Optional[dict] = None     # set by the traced run


def warm_budgets(eng):
    """Compile every token budget through the public path: for each, an
    otherwise empty engine gets prompts whose lengths sum to the budget,
    so the first step packs exactly that many tokens."""
    chunk = eng.chunk_size
    prev = 0
    for budget in eng.token_budgets:
        total = min(budget, eng.max_batch_size * chunk)
        sizes = [chunk] * (total // chunk)
        if total % chunk:
            sizes.append(total % chunk)
        if not (prev < total <= budget) or len(sizes) > eng.max_batch_size \
                or max(sizes) + 2 > eng.max_seq_len:
            raise RuntimeError(f"cannot pack {total} tokens for the budget "
                               f"{budget} of {eng.token_budgets}")
        for n in sizes:
            eng.add_request(np.ones(n, np.int64), 2)
        eng.run_to_completion()
        prev = budget
    missing = set(eng.token_budgets) - set(eng.mixed.compile_counts)
    if missing:
        raise RuntimeError(f"budgets never compiled in warm-up: {missing}")
    eng.finished.clear()


class _CompileCounter:
    """Counts XLA backend compilations through JAX's monitoring hook."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def run_window(eng, plan: Plan, trace_span=None, annotate=None):
    """Offer ``plan`` to ``eng``: ``plan.warmup_s`` of unmeasured traffic
    to reach a steady state, ``plan.seconds`` measured, then a drain that
    offers nothing.  ``trace_span=(after_s, for_s, start, stop)`` runs
    ``start()`` that long after the window opens and ``stop()`` ``for_s``
    later, between two steps (``after_s`` may be negative: a part of the
    warm-up)."""
    clock = time.perf_counter
    compiles = _CompileCounter()
    tracks: List[Track] = []
    steps: List[StepRec] = []
    active: List[Track] = []

    def send(tr: Track):
        tr.sent = clock()
        tr.rid = eng.add_request(tr.req.prompt, tr.req.n_out)
        tr.handle = eng.waiting[-1]
        if tr.handle.req_id != tr.rid:
            raise RuntimeError("the engine's waiting list does not end in "
                               "the request just added")
        active.append(tr)

    def step():
        t0 = clock()
        if annotate is not None:
            with annotate("bench.engine_step"):
                eng.step()
        else:
            eng.step()
        t1 = clock()
        n_dec = n_pre = 0
        spans = []
        for tr in active:
            h = tr.handle
            new = len(h.output_ids) - len(tr.token_times)
            pre = h.prefill_pos - tr.seen_prefill
            if pre > 0:
                spans.append((pre, h.prefill_pos))
                n_pre += pre
                tr.seen_prefill = h.prefill_pos
            elif new > 0:
                spans.append((1, len(tr.req.prompt) + len(h.output_ids) - 1))
                n_dec += 1
            if new > 0:
                tr.token_times.extend([t1] * new)
        steps.append(StepRec(t0, t1, n_dec, n_pre, spans,
                             sum(s is not None for s in eng.slots)))
        finished = [tr for tr in active if tr.done]
        if finished:
            active[:] = [tr for tr in active if not tr.done]
        return finished

    t_start = clock()
    w0 = t_start + plan.warmup_s
    w1 = w0 + plan.seconds
    pending: List[Track] = []        # sorted by due
    if plan.loop == "open":
        pending = [Track(r, t_start + r.due) for r in plan.requests]
    else:
        pool = plan.requests
        cursor = 0
        for c in range(plan.clients):
            r = pool[cursor % len(pool)]
            cursor += 1
            # spread the clients' phases: the first prompts are cut to
            # k/clients of their length (unmeasured warm-up requests)
            keep = max(16, len(r.prompt) * (c + 1) // plan.clients)
            r = Request(r.index, r.prompt[:keep], r.n_out, client=c)
            pending.append(Track(r, t_start))
    tracks.extend(pending)
    compiles_at_w0 = None
    tracing = 0                      # 0 not yet, 1 running, 2 done

    while True:
        now = clock()
        if compiles_at_w0 is None and now >= w0:
            compiles_at_w0 = compiles.count
        if now >= w1:
            break
        if trace_span is not None:
            after_s, for_s, start, stop = trace_span
            if tracing == 0 and now >= w0 + after_s:
                start()
                tracing, trace_t0 = 1, clock()
                stalls = [trace_t0 - now]
            elif tracing == 1 and now >= trace_t0 + for_s:
                trace_t1 = clock()
                stop()
                tracing = 2
                stalls.append(clock() - trace_t1)
        while pending and pending[0].due <= now:
            send(pending.pop(0))
        if eng.has_work():
            for tr in step():
                if plan.loop == "closed":
                    t = clock()
                    if t < w1:
                        r = pool[cursor % len(pool)]
                        cursor += 1
                        nxt = Track(Request(r.index, r.prompt, r.n_out,
                                            client=tr.req.client), t)
                        tracks.append(nxt)
                        pending.append(nxt)
        else:
            nxt_due = pending[0].due if pending else w1
            time.sleep(max(0.0, min(nxt_due, w1) - clock()))
    if tracing == 1:
        trace_t1 = clock()
        stop()
        tracing = 2
    # due inside the window but behind a step that ran past its end:
    # offered late, not dropped
    while plan.loop == "open" and pending and pending[0].due < w1:
        send(pending.pop(0))
    compiles_in_window = compiles.count - (compiles_at_w0 or 0)
    for tr in tracks:
        tr.measured = tr.handle is not None and w0 <= tr.due < w1
    # drain: nothing more is offered; what was offered runs to its end
    limit = clock() + DRAIN_LIMIT_S
    while eng.has_work() and clock() < limit:
        step()
    res = WindowResult(t_start, w0, w1, tracks, steps, compiles_in_window,
                       drained=not eng.has_work())
    if tracing == 2:
        res.trace = {"t0": trace_t0, "t1": trace_t1,
                     "profiler_stalls_s": stalls}
    return res
