"""`ckpt_hash_s`: seconds of the program's `ckpt.hash` spans (each device
leaf's chunks hashed, deduplicated and the new ones copied) per
`ckpt.put`, i.e. per checkpoint."""


def read(ctx):
    p = ctx["program"]
    puts = p["counts"].get("ckpt.put", 0)
    return p["totals"].get("ckpt.hash", 0.0) / puts if puts else None
