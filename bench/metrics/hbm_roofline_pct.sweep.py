"""Kernel layer: the sweep's share of the HBM roofline.  The least bytes
the program needs per sweep (every input read once, every output's
valid region written once; :func:`bench.counts.least_bytes`) over the
device's peak HBM bandwidth, divided by the device busy time per sweep
of every operation, kernel and glue alike."""


def read(ctx):
    n = ctx.counters.get("sweeps")
    if not n or ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    least_s = ctx.least_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ctx.trace.busy_s / n)
