"""Scheduler: due time to the engine's own ``admit`` span (request_trace),
95th percentile over the requests due in the window."""
from harness.result import percentile


def read(run):
    return percentile(((t.admit - t.due) * 1e3 for t in run.measured()
                       if t.admit is not None), 95)
