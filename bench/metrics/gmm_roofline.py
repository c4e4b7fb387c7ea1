"""`gmm_roofline`: percent of its roofline that the expert kernel reached
in the traced window: the least time of the window's grouped matmuls
(``counts/<flops>.py`` ``gmm_roofline_s``, rows from counter `moe.rows`,
per block and step from `moe.block_steps`) over their device self-time.

The kernel runs as Pallas ``custom-call`` instructions that the optimized
HLO names ``gmm``, ``gmm.<n>`` (products) and ``tgmm``, ``tgmm.<n>``
(weight gradients); the step's other custom calls (``AllocateBuffer``,
``ConcatBitcast``) are left out by name."""
import re

from bench import spec

KERNEL = re.compile(r"^t?gmm(\.\d+)? custom-call$")    # as trace.op_name names it


def read(ctx):
    t, c = ctx["trace"], ctx["program"]["counters"]
    rows, blocks = c.get("moe.rows", 0), c.get("moe.block_steps", 0)
    if t is None or ctx["peak"] is None or not (rows and blocks):
        return None
    times = [sum(s for name, s in d["op_time"].items() if KERNEL.match(name))
             for d in t.devices.values() if d["op_time"]]
    kernel_s = sum(times) / len(times) * 1e-9 if times else 0.0
    if not kernel_s:
        return None
    config = ctx["cell"].config
    least = spec.counts(config["flops"]).gmm_roofline_s(
        config["model"], rows, blocks, ctx["peak"])
    return 100.0 * least / kernel_s
