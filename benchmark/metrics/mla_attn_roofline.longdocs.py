"""Kernels: ``ragged_latent_attention`` (every kernel whose name holds
``latent_attention``, summed).  The least time the chip could take for
the traced steps' latent attention (``deepseek_v2_counts``: per span the
cheaper of the absorbed and the expanded form's FLOPs; each span's latent
rows read once, q and o once at their published widths; the larger of
FLOPs over peak and bytes over bandwidth, step by step, per layer) over
the device time of the kernel's events in the trace, in percent."""
import os

from harness import counts as shared, spec, xtrace


def read(run):
    if run.trace is None:
        return None
    spent = xtrace.kernel_seconds(run.trace, "latent_attention")
    steps = [s for s in run.traced_steps() if s.spans]
    if not spent or not steps:
        return None
    c = spec._module("bench_deepseek_v2_counts",
                     os.path.dirname(os.path.abspath(__file__)),
                     "deepseek_v2_counts.py")
    least = sum(shared.roofline_seconds(
        c.attention_flops(run.config, s.spans),
        c.attention_bytes(run.config, s.spans), run.peaks)[0]
        for s in steps) * run.config["num_hidden_layers"]
    return 100.0 * least / spent
