"""`restore_built_share`: percent of the window's restored device bytes
(counter `restore.bytes`) copied into a newly built array
(`restore.bytes_built`); the rest reused an array already built in the
same restore."""


def read(ctx):
    c = ctx["program"]["counters"]
    total = c.get("restore.bytes", 0)
    return 100.0 * c.get("restore.bytes_built", 0) / total if total else None
