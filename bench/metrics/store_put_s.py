"""`store_put_s`: mean seconds of the window's `store_put` spans (`probes.py`)."""


def read(ctx):
    spans = ctx["spans"].get("store_put")
    return sum(spans) / len(spans) if spans else None
