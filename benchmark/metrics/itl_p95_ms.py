"""End to end: 95th percentile over every gap between consecutive output
tokens of every request due in the window (all gaps pooled, no per-request
mean)."""
from harness.result import percentile


def read(run):
    gaps = []
    for t in run.measured():
        tt = t.token_times
        gaps.extend((b - a) * 1e3 for a, b in zip(tt, tt[1:]))
    return percentile(gaps, 95)
