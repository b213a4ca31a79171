"""Scheduler: real tokens over the padded token budget the engine
launched (``tokens`` and ``budget`` of its step records), summed over the
launched steps that ended in the window, in percent.  The rest of every
launch is padding."""
from harness.program_spans import budget_fill as read  # noqa: F401
