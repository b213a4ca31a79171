"""Model step: the time the traced steps' unavoidable HBM traffic takes at
the chip's peak bandwidth (``counts.serve_step_bytes``: weights once, live
keys and values once) over the device's busy time in the traced part."""
from harness import counts


def read(run):
    if run.trace is None or not run.trace["busy_s"]:
        return None
    steps = run.traced_steps()
    if not steps:
        return None
    nbytes = sum(counts.serve_step_bytes(run.config, s.spans)
                 for s in steps if s.spans)
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / run.trace["busy_s"]
