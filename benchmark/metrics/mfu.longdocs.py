"""Model step: FLOPs that the tokens processed in the window need
(``deepseek_v2_counts.step_flops``: 2 x the matmul parameters every token
multiplies, attention in its published factorised shapes, 2 x an expert's
parameters a row a HELD expert was given (the step record's ``moe_rows``),
latent attention as the cheaper of its two forms span by span, the head
over sampled rows) over the chip's peak times the window, in percent."""
import os

from harness import program_spans, readers, spec


def read(run):
    records = program_spans.step_records(run)
    steps = run.steps_in()
    if not records or run.peaks is None \
            or any("moe_rows" not in r for r in records):
        return None
    c = spec._module("bench_deepseek_v2_counts",
                     os.path.dirname(os.path.abspath(__file__)),
                     "deepseek_v2_counts.py")
    flops = sum(c.step_flops(run.config, s.spans,
                             sum(1 for q, _ in s.spans if q == 1),
                             r["moe_rows"])
                for s, r in zip(steps, records))
    # the head row of each prompt's last chunk
    flops += (2 * c.head_params(run.config)
              * readers._first_tokens_in_window(run))
    return 100.0 * flops / (run.peaks["flops_per_s"] * run.window_s)
