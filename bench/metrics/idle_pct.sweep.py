"""Device layer: percent of the traced window in which no operation ran
on the device."""


def read(ctx):
    return ctx.trace.idle_pct() if ctx.trace is not None else None
