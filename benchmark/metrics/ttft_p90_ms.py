"""End to end: 90th percentile, over every request due in the window, of
first-token time minus the time the request was DUE (not sent, not
admitted).  A request with no first token has none and counts as failed."""
from harness.result import percentile


def read(run):
    return percentile(((t.token_times[0] - t.due) * 1e3
                       for t in run.measured() if t.token_times), 90)
