"""Model step: the benchmark's span round ``engine.step()``, median over
the steps that ended in the window."""
from harness.result import percentile


def read(run):
    return percentile(((s.t1 - s.t0) * 1e3 for s in run.steps_in()), 50)
