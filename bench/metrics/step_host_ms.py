"""`step_host_ms`: host milliseconds per step outside the device: mean
of the program's `step.batch` span (the batch made and put on the device)
plus mean of `step.dispatch` (a built step program's call, until it
returns).  First calls are `step.build.*` and not counted."""


def read(ctx):
    p = ctx["program"]
    nb = p["counts"].get("step.batch", 0)
    nd = p["counts"].get("step.dispatch", 0)
    if not (nb and nd):
        return None
    return 1e3 * (p["totals"]["step.batch"] / nb
                  + p["totals"]["step.dispatch"] / nd)
