"""Kernels: ``ragged_gated_delta``.  The least time the chip could take
for the traced steps' gated delta rule (``olmo_hybrid_counts``: per span
the cheaper of the token-by-token and the chunked form's FLOPs; each
span's state read and written once in float32, its rows' q, k, v, beta,
g and o once in the served type; the larger of FLOPs over peak and bytes
over bandwidth, step by step, per linear layer) over the device time of
the kernel's events in the trace, in percent."""
import os

from harness import counts as shared, spec, xtrace


def read(run):
    if run.trace is None:
        return None
    spent = xtrace.kernel_seconds(run.trace, "ragged_gated_delta")
    steps = [s for s in run.traced_steps() if s.spans]
    if not spent or not steps:
        return None
    c = spec._module("bench_olmo_hybrid_counts",
                     os.path.dirname(os.path.abspath(__file__)),
                     "olmo_hybrid_counts.py")
    least = sum(shared.roofline_seconds(
        c.gdn_flops(run.config, s.spans),
        c.gdn_bytes(run.config, s.spans), run.peaks)[0]
        for s in steps) * c.kinds(run.config)[0]
    return 100.0 * least / spent
