"""`decide_ms`: mean milliseconds of the program's `decide` span
(`ElasticPolicy.decide`), once per tick."""


def read(ctx):
    p = ctx["program"]
    n = p["counts"].get("decide", 0)
    return 1e3 * p["totals"]["decide"] / n if n else None
