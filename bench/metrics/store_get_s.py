"""`store_get_s`: mean seconds of the window's `store_get` spans (`probes.py`)."""


def read(ctx):
    spans = ctx["spans"].get("store_get")
    return sum(spans) / len(spans) if spans else None
