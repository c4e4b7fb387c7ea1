"""`restore_read_s`: mean seconds of the program's `restore.read` span: one
worker's device state rebuilt from the checkpoint store."""


def read(ctx):
    p = ctx["program"]
    n = p["counts"].get("restore.read", 0)
    return p["totals"]["restore.read"] / n if n else None
