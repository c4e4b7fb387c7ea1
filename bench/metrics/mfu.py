"""`mfu`: model FLOPs per token (``flops.py``) times the window's tokens
per second, over the chip's peak bf16 FLOP/s (``peaks.py``), in percent."""


def read(ctx):
    if not ctx["steps"] or ctx["peak"] is None:
        return None
    rate = ctx["tokens"] / ctx["window_s"]
    return 100.0 * ctx["flops_per_token"] * rate / ctx["peak"]["bf16_flops"]
