"""`preempt_s`: mean seconds of the window's preempt events (`probes.py`)."""


def read(ctx):
    ev = ctx["events"].get("preempt")
    return sum(ev) / len(ev) if ev else None
