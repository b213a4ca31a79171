"""Load generator: how late it sent (sent minus due), 95th percentile.
The generator shares a thread with the engine, so this is the part of a
step that a request due in mid-step waits out before it is even queued."""
from harness.result import percentile


def read(run):
    return percentile(((t.sent - t.due) * 1e3 for t in run.measured()), 95)
