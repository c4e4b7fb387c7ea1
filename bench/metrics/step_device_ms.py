"""`step_device_ms`: device milliseconds per run of the jitted step
program in the traced window (``trace.Reduced.program_ms``)."""


def read(ctx):
    t = ctx["trace"]
    return None if t is None else t.program_ms(ctx["step_program"])
