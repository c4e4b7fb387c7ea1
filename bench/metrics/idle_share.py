"""`idle_share`: percent of the traced window in which no operation ran on
the device (``trace.Reduced.idle_share``)."""


def read(ctx):
    t = ctx["trace"]
    share = None if t is None else t.idle_share()
    return None if share is None else 100.0 * share
