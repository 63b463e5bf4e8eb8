"""PlanServe ``_execute`` (pad, stack, device call, copy-back): mean over
batches of ``latency_ms - queue_wait_ms``, which every request of one
batch reports alike; each request is weighted by one over its batch's
size, so each batch counts once."""


def read(ctx):
    stats = list(ctx.counters.get("stats", ()))
    if not stats:
        return None
    weights = [1.0 / s["batch_size"] for s in stats]
    total = sum(w * (s["latency_ms"] - s["queue_wait_ms"]) for w, s in zip(weights, stats))
    return total / sum(weights)
