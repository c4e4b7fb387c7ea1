"""`setup_s`: seconds from the process's start until the window opens:
imports, the executor and its jobs' state, every step program (compiled
or loaded from the cache) and the set-up ticks the reference follows."""


def read(ctx):
    return ctx["setup_s"]
