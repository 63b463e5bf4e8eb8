"""Host-half layer (``execute_plan``'s pad, slice and copy around the
kernel): device time of every operation that is not an ``hfav_*``
kernel, per sweep, from the profiler trace."""


def read(ctx):
    n = ctx.counters.get("sweeps")
    if not n or ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    return ctx.trace.glue_s * 1e3 / n
