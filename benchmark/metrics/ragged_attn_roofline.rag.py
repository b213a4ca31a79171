"""Kernels: ``ragged_paged_attention`` in the full-attention layers (one
group: 30 query heads over 30 kv heads, no rope).  The least time the
chip could take for the traced steps' attention (``olmo_hybrid_counts``:
FLOPs and bytes from each pack's real span and context lengths at the
published heads; the larger of FLOPs over peak and bytes over bandwidth,
step by step, per full layer) over the device time of the kernel's events
in the trace, in percent."""
import os

from harness import counts as shared, spec, xtrace


def read(run):
    if run.trace is None:
        return None
    spent = xtrace.kernel_seconds(run.trace, "ragged_paged_attention")
    steps = [s for s in run.traced_steps() if s.spans]
    if not spent or not steps:
        return None
    c = spec._module("bench_olmo_hybrid_counts",
                     os.path.dirname(os.path.abspath(__file__)),
                     "olmo_hybrid_counts.py")
    least = sum(shared.roofline_seconds(
        c.attention_flops(run.config, s.spans),
        c.attention_bytes(run.config, s.spans), run.peaks)[0]
        for s in steps) * c.kinds(run.config)[1]
    return 100.0 * least / spent
