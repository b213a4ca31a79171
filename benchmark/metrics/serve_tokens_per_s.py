"""End to end: prompt tokens whose prefill finished in the window plus
output tokens emitted in the window, over the window's seconds.  Counted
chunk by chunk from every step that ended in the window, whoever the
request belongs to."""
from harness.readers import serve_tokens


def read(run):
    if not run.steps_in():
        return None
    return serve_tokens(run) / run.window_s
