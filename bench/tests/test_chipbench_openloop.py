"""The open-loop schedule and the tail over all requests."""
import math

import numpy as np
import pytest

from bench import openloop


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5, 2**40 + 3])
def test_schedule_is_poisson_spaced_and_seeds_only_reorder(seed):
    due = openloop.schedule(8.0, 30.0, seed)
    assert len(due) == 240
    assert due[0] == 0.0 and np.all(np.diff(due) > 0) and due[-1] < 30.0
    gaps = np.sort(np.diff(np.append(due, 30.0)))
    other = openloop.schedule(8.0, 30.0, seed + 1)
    assert np.allclose(gaps, np.sort(np.diff(np.append(other, 30.0))))
    assert not np.allclose(due, other)
    # the same cycle of gaps, rotated by one
    assert np.allclose(np.roll(np.diff(np.append(due, 30.0)), 1),
                       np.diff(np.append(other, 30.0)))
    # exponential gaps: mean 1/rate, about as many under the median as
    # an exponential has (1 - e^-1 of them under the mean)
    assert math.isclose(gaps.mean(), 1 / 8.0, rel_tol=1e-9)
    assert np.mean(gaps < gaps.mean()) == pytest.approx(1 - math.exp(-1), abs=0.02)
    assert np.array_equal(due, openloop.schedule(8.0, 30.0, seed))


def test_served_counts_the_batch_in_service_pro_rata():
    t_end = 10.0
    done = np.array([2.0, 9.0, 9.0, 11.0, 11.0, 12.0, np.nan])
    service = np.array([1.0, 2.0, 2.0, 4.0, 4.0, 1.0, 1.0])
    # three answered by t_end; two three quarters through their service,
    # (10 - 7) / 4; one not started; one never answered
    assert openloop.served(done, service, t_end) == pytest.approx(3 + 2 * 0.75)
    assert openloop.served(done[:3], service[:3], t_end) == 3


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert openloop.percentile(values, 95) == 95
    assert openloop.percentile(values, 50) == 50
    assert openloop.percentile([7.0], 95) == 7.0
    assert openloop.percentile(list(range(1, 21)), 95) == 19
    with pytest.raises(ValueError):
        openloop.percentile([], 95)


def test_p95_counts_failed_requests_as_missing_the_tail():
    due = np.arange(20, dtype=float)
    done = due + 0.1
    assert openloop.percentile(openloop.latencies_ms(due, done, 100.0), 95) \
        == pytest.approx(100.0)
    done[[3, 11]] = np.nan  # two of twenty never answered: p95 is theirs
    lat = openloop.latencies_ms(due, done, 100.0)
    assert lat[3] == pytest.approx(97_000.0) and lat[11] == pytest.approx(89_000.0)
    assert openloop.percentile(lat, 95) == pytest.approx(89_000.0)
