"""Model step: FLOPs that the tokens processed in the window need
(``counts.serve_step_flops``: 2 x active matmul parameters a token, causal
attention, the head over sampled rows; the embedding lookup counts
nothing) over the chip's peak times the window, in percent."""
from harness.readers import serve_mfu as read  # noqa: F401
