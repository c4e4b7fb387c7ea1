"""`resume_s`: mean seconds of the window's resume events (`probes.py`)."""


def read(ctx):
    ev = ctx["events"].get("resume")
    return sum(ev) / len(ev) if ev else None
