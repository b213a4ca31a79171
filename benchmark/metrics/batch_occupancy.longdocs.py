"""Scheduler: occupied slots over ``max_batch_size``, mean over the steps
that ended in the window, in percent."""


def read(run):
    steps = run.steps_in()
    if not steps:
        return None
    slots = run.cell.deploy["engine"]["max_batch_size"]
    return 100.0 * sum(s.running for s in steps) / (len(steps) * slots)
