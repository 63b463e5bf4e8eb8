"""PlanServe micro-batcher: 95th percentile (nearest rank) of the
``queue_wait_ms`` that each answered request's ticket reports: from its
submit to the start of its batch's execution."""
import math


def read(ctx):
    waits = sorted(s["queue_wait_ms"] for s in ctx.counters.get("stats", ()))
    if not waits:
        return None
    return waits[max(0, math.ceil(0.95 * len(waits)) - 1)]
