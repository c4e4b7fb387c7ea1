"""`resize_build_s`: mean seconds of the program's `step.build.resize`
span: the first call of the step program at a new splice."""


def read(ctx):
    p = ctx["program"]
    n = p["counts"].get("step.build.resize", 0)
    return p["totals"]["step.build.resize"] / n if n else None
