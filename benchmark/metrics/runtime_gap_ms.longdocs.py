"""Device: over the traced seconds, (idle seconds - the host gaps of the
launched pairs there) per pair, milliseconds: the mean idle per launch
that no host phase fills (the fetch's wake-up after the device finished,
the device's start after the enqueue).  Sums of durations, no shared
clock."""
from harness.program_gaps import runtime_gap_ms as read  # noqa: F401
