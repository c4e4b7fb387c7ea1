"""`resume_build_s`: mean seconds of the program's `step.build.restore`
span: the first call of a restored runtime's step program (trace, lowering,
compile or cache load, enqueue)."""


def read(ctx):
    p = ctx["program"]
    n = p["counts"].get("step.build.restore", 0)
    return p["totals"]["step.build.restore"] / n if n else None
