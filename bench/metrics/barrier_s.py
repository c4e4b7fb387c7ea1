"""`barrier_s`: mean seconds of the program's `preempt.barrier` span over the
window's preempts: the preempt request until the barrier steps have run."""


def read(ctx):
    p = ctx["program"]
    n = p["counts"].get("preempt.barrier", 0)
    return p["totals"]["preempt.barrier"] / n if n else None
