"""Kernel layer: device time of the ``hfav_*`` stencil kernels per sweep,
from the profiler trace."""


def read(ctx):
    n = ctx.counters.get("sweeps")
    if not n or ctx.trace is None or ctx.trace.kernel_s <= 0:
        return None
    return ctx.trace.kernel_s * 1e3 / n
