"""Scheduler: share of the window's launched steps whose record has
``n_pre`` > 0, in percent.  Over 5%, ``itl_p95_ms`` is the length of a
step that carries a chunk; under 5% it is a decode-only step's."""
from harness.program_spans import chunk_step_share as read  # noqa: F401
