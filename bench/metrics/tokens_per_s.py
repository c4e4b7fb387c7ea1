"""`tokens_per_s`: tokens of every step that advanced a job's state in the
window, over all jobs, divided by the window's seconds (host clock; the
window ends with the tick that crosses its length, and every step's loss
has been read back by then)."""


def read(ctx):
    return ctx["tokens"] / ctx["window_s"] if ctx["steps"] else None
