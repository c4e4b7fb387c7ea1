"""`ckpt_new_share`: percent of the window's checkpointed device bytes
(counter `ckpt.bytes`) that were new to the store (`ckpt.bytes_new`);
the rest was deduplicated."""


def read(ctx):
    c = ctx["program"]["counters"]
    total = c.get("ckpt.bytes", 0)
    return 100.0 * c.get("ckpt.bytes_new", 0) / total if total else None
