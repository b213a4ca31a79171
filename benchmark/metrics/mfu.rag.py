"""Model step: FLOPs that the tokens processed in the window need
(``olmo_hybrid_counts.step_flops``: 2 x the matmul parameters every token
multiplies, the convolution, the gated delta rule as the cheaper of its
two forms span by span, causal attention in the full layers, the head
over sampled rows) over the chip's peak times the window, in percent."""
import os

from harness import readers, spec


def read(run):
    steps = run.steps_in()
    if not steps or run.peaks is None:      # no chip, no peak
        return None
    c = spec._module("bench_olmo_hybrid_counts",
                     os.path.dirname(os.path.abspath(__file__)),
                     "olmo_hybrid_counts.py")
    flops = sum(c.step_flops(run.config, s.spans,
                             sum(1 for q, _ in s.spans if q == 1))
                for s in steps)
    # the head row of each prompt's last chunk
    flops += (2 * c.head_params(run.config)
              * readers._first_tokens_in_window(run))
    return 100.0 * flops / (run.peaks["flops_per_s"] * run.window_s)
