"""Scheduler: the engine's own step record, median over the launched
steps that ended in the window of the step less its launch, ``(t_end -
t0) - (t_tokens - t_fill)``: admission, packing, filling the pack and
bookkeeping."""
from harness.program_spans import engine_host_ms as read  # noqa: F401
