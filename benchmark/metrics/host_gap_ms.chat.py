"""Scheduler: median over the window's consecutive launched steps of the
engine's own ``t_dispatch[N+1] - t_tokens[N]``: the host's time from one
step's tokens on the host to the next launch enqueued (bookkeeping, the
caller's loop, admission, packing, filling, the operands and the jitted
call).  What an overlap of host and device (ROADMAP S3) hides and a
smaller launch (D4) shortens."""
from harness.program_gaps import host_gap_ms as read  # noqa: F401
