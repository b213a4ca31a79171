"""End to end: process start to the first measured instant (imports,
building the model, compiling or loading every program, warm-up traffic
up to the steady state)."""


def read(run):
    return run.setup_s
