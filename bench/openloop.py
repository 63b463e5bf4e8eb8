"""Open-loop arrivals and the tail over all requests.

Requests are due on a schedule that does not wait for answers, as
independent users submit them.  Every request is timed from when it was
due, so a stall also counts against the requests that queue behind it,
and the generator's own lateness is reported beside the result.
"""
from __future__ import annotations

import math

import numpy as np


#: The one order of the gaps that every run's arrivals follow: a seeded
#: shuffle fixed for good, so its bursts are a Poisson stream's.
GAP_ORDER_SEED = 0


def schedule(rate_rps: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of a Poisson stream of
    ``rate_rps`` over ``seconds``.

    The gaps are the exponential quantiles ``-ln(1 - (m + 1/2) / n) /
    rate``, scaled to sum to ``seconds``, in one fixed shuffled order;
    the seed only rotates that cycle, choosing where in it the window
    starts.  So every seed offers the same arrivals and the same bursts
    in another order: near the knee the tail depends on how the bursts
    fall, and a seeded shuffle per run made the p95 of two seeds differ
    far more than two runs of one seed.  The first request is due at 0
    and the last one gap before the window ends.
    """
    n = max(1, round(rate_rps * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    np.random.default_rng(GAP_ORDER_SEED).shuffle(gaps)
    gaps = np.roll(gaps, seed % n)
    return np.cumsum(gaps) - gaps


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of no values")
    return float(v[max(0, math.ceil(q / 100 * v.size) - 1)])


def served(done: np.ndarray, service_s: np.ndarray, t_end: float) -> float:
    """Requests served by ``t_end``: each answered by then counts 1, and
    each whose batch was still executing then counts the share of its
    service time (``service_s``, from its batch's start to its answer)
    that fell before ``t_end``.  Answers arrive a whole batch at a time,
    so whole answers alone would swing by up to a batch with where the
    window's end falls in a batch's service."""
    done = np.asarray(done, np.float64)
    start = done - np.asarray(service_s, np.float64)
    part = np.clip((t_end - start) / np.maximum(done - start, 1e-12), 0.0, 1.0)
    return float(np.sum(np.where(np.isnan(done), 0.0, np.where(done <= t_end, 1.0, part))))


def latencies_ms(due: np.ndarray, done: np.ndarray, gave_up: float) -> np.ndarray:
    """Latency of every request from when it was due, in ms.  A request
    that failed or never finished (``done`` NaN) counts with the time at
    which the benchmark gave up on it, so it misses any limit a finished
    one could meet."""
    done = np.where(np.isnan(done), gave_up, done)
    return (done - due) * 1e3
