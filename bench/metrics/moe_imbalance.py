"""`moe_imbalance`: the busiest held expert's rows over the mean held
expert's, per MoE block and step, over the window: counter `moe.rows_peak`
times the held experts over `moe.rows`.  1 is even; the expert kernel's
grid follows the rows, so a busy expert lengthens it."""


def read(ctx):
    c = ctx["program"]["counters"]
    rows = c.get("moe.rows", 0)
    if not rows:
        return None
    moe = ctx["cell"].config["model"]["moe"]
    held = moe.get("num_held") or moe["num_experts"]
    return c.get("moe.rows_peak", 0) * held / rows
