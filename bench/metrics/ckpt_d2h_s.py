"""`ckpt_d2h_s`: mean seconds of the program's `ckpt.d2h` span: a checkpoint's
state copied from the device to host numpy."""


def read(ctx):
    p = ctx["program"]
    n = p["counts"].get("ckpt.d2h", 0)
    return p["totals"]["ckpt.d2h"] / n if n else None
