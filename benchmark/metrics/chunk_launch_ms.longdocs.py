"""Model step: median ``t_tokens - t_fill`` of the engine's step records
(the whole of ``call_packed``: the pack's transfer, the enqueue, the wait
for the device, the token ids back) over the window's steps that carried
prefill tokens (``n_pre`` > 0)."""
from harness.program_spans import step_launch_ms


def read(run):
    return step_launch_ms(run, chunk=True)
