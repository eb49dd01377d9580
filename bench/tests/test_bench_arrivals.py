"""The traffic generator, the exact percentile, and latency counted from
when a request was due (with the generator's lateness reported)."""
import time
from concurrent.futures import Future

import numpy as np
import pytest

from bench import arrivals, cell

MIX = {"driver": "open_loop", "rate_per_s": 200, "gap_seed": 0}


def test_percentile_is_exact_nearest_rank():
    values = list(range(1, 101))               # 1..100
    assert arrivals.percentile(values, 95) == 95
    assert arrivals.percentile(values, 50) == 50
    assert arrivals.percentile(values, 100) == 100
    assert arrivals.percentile([3.5], 95) == 3.5
    assert arrivals.percentile([5, 1, 4, 2, 3], 95) == 5
    assert arrivals.percentile([5, 1, 4, 2, 3], 40) == 2
    with pytest.raises(ValueError):
        arrivals.percentile([], 95)
    with pytest.raises(ValueError):
        arrivals.percentile([1.0], 0)


def test_every_seed_offers_the_same_work():
    a = arrivals.schedule(MIX, 1, 10.0, pool=64)
    b = arrivals.schedule(MIX, 2**31 + 99, 10.0, pool=64)
    assert len(a.offsets_s) == len(b.offsets_s) == 2000
    gaps_a = np.sort(np.diff(np.append(a.offsets_s, 10.0)))
    gaps_b = np.sort(np.diff(np.append(b.offsets_s, 10.0)))
    np.testing.assert_allclose(gaps_a, gaps_b, rtol=1e-9, atol=1e-12)
    assert a.offsets_s[0] == 0.0 and a.offsets_s[-1] < 10.0
    assert np.all(np.diff(a.offsets_s) >= 0)
    assert not np.array_equal(a.offsets_s, b.offsets_s)
    assert not np.array_equal(a.members, b.members)
    assert a.members.min() >= 0 and a.members.max() < 64


def test_same_seed_same_schedule():
    a = arrivals.schedule(MIX, 5, 3.0, pool=8)
    b = arrivals.schedule(MIX, 5, 3.0, pool=8)
    np.testing.assert_array_equal(a.offsets_s, b.offsets_s)
    np.testing.assert_array_equal(a.members, b.members)


def test_no_request_raises():
    with pytest.raises(ValueError):
        arrivals.schedule(dict(MIX, rate_per_s=0.01), 1, 1.0, pool=1)


class _SlowServer:
    """Answers each request ``delay`` after it is submitted, on a timer
    thread, as the engine's dispatcher would."""

    def __init__(self, delay):
        import threading
        self.delay = delay
        self.threading = threading

    def submit(self, weights, x, t):
        fut = Future()
        self.threading.Timer(self.delay, fut.set_result, (x,)).start()
        return fut


def test_latency_runs_from_due_time_and_lateness_is_reported():
    sched = arrivals.Schedule(offsets_s=np.array([0.0, 0.0, 0.0, 0.05]),
                              members=np.zeros(4, int))
    pool = [np.zeros(2)]
    start = time.perf_counter() + 0.02
    futs, done, late = cell._due_and_done(_SlowServer(0.03), None, pool,
                                          sched, start, 1)
    for f in futs:
        f.result(timeout=5)
    time.sleep(0.01)
    lat = [d - (start + o) for d, o in zip(done, sched.offsets_s)]
    assert all(lt >= 0.03 - 1e-3 for lt in lat)      # counted from due
    assert all(lt >= -1e-3 for lt in late)
    assert len(late) == 4 and late.max() < 0.05


class _SlowSubmitServer:
    """Takes ``cost`` seconds of the caller's thread per submit and
    answers at once: a starved generator."""

    def __init__(self, cost):
        self.cost = cost

    def submit(self, weights, x, t):
        time.sleep(self.cost)
        fut = Future()
        fut.set_result(x)
        return fut


def test_a_late_generator_shows_in_lateness_and_latency():
    sched = arrivals.Schedule(offsets_s=np.zeros(5), members=np.zeros(5, int))
    start = time.perf_counter()
    futs, done, late = cell._due_and_done(_SlowSubmitServer(0.01), None,
                                          [np.zeros(1)], sched, start, 1)
    assert late[-1] >= 0.035                  # four submits ahead of it
    lat = np.array(done) - start
    assert np.all(lat >= late - 1e-9)         # the wait counts
