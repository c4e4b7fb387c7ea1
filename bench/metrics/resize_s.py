"""`resize_s`: mean seconds of the window's resize events (`probes.py`)."""


def read(ctx):
    ev = ctx["events"].get("resize")
    return sum(ev) / len(ev) if ev else None
