"""Reductions that several per-layer metrics share.  A metric's own file
under ``metrics/`` says what it is and calls one of these; a reader that
finds nothing to read returns ``None`` and the metric is left out."""
from __future__ import annotations

from . import counts, xtrace


def _first_tokens_in_window(run) -> int:
    w = run.window
    return sum(1 for t in w.tracks
               if t.token_times and w.w0 <= t.token_times[0] < w.w1)


def serve_tokens(run) -> int:
    """Prompt tokens prefilled and output tokens emitted by the steps that
    ended in the window (a prompt's last chunk also emits a token)."""
    return (sum(s.prefill_tokens + s.decode_tokens for s in run.steps_in())
            + _first_tokens_in_window(run))


def serve_mfu(run):
    steps = run.steps_in()
    if not steps or run.peaks is None:      # no chip, no peak
        return None
    flops = sum(counts.serve_step_flops(
        run.config, s.spans, sum(1 for q, kv in s.spans if q == 1))
        for s in steps)
    # the head row of each prompt's last chunk
    flops += (2 * counts.head_params(run.config)
              * _first_tokens_in_window(run))
    return 100.0 * flops / (run.peaks["flops_per_s"] * run.window_s)


def ragged_attention_roofline(run, kernel="ragged_paged_attention"):
    if run.trace is None:
        return None
    spent = xtrace.kernel_seconds(run.trace, kernel)
    steps = [s for s in run.traced_steps() if s.spans]
    if not spent or not steps:
        return None
    least = sum(counts.roofline_seconds(
        counts.attention_flops(run.config, s.spans),
        counts.attention_bytes(run.config, s.spans), run.peaks)[0]
        for s in steps) * run.config["num_hidden_layers"]
    return 100.0 * least / spent


def device_idle(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
