import threading
import time

import numpy as np
import pytest

from harness import openloop

MIX = {
    "rate_rps": 400,
    "arrivals": {"process": "poisson"},
    "vertex": {"dist": "zipf", "s": 1.0},
    "seeds_per_request": {"values": [1, 2, 4, 8, 16], "weights": [0.6, 0.2, 0.1, 0.07, 0.03]},
}


def test_same_seed_same_schedule_other_seed_other_schedule():
    a = openloop.make_schedule(5, MIX, 1000, 2.0)
    b = openloop.make_schedule(5, MIX, 1000, 2.0)
    c = openloop.make_schedule(6, MIX, 1000, 2.0)
    assert np.array_equal(a.due_s, b.due_s)
    assert all(np.array_equal(x, y) for x, y in zip(a.ids, b.ids))
    assert len(a.due_s) != len(c.due_s) or not np.array_equal(a.due_s, c.due_s)


def test_poisson_rate_sizes_and_hot_vertices():
    s = openloop.make_schedule(1, MIX, 1000, 20.0)
    assert len(s.due_s) == pytest.approx(8000, rel=0.05)
    assert np.all(np.diff(s.due_s) >= 0) and s.due_s[-1] < 20.0
    sizes = np.asarray([len(x) for x in s.ids])
    assert set(sizes.tolist()) <= {1, 2, 4, 8, 16}
    assert np.mean(sizes == 1) == pytest.approx(0.6, abs=0.03)
    flat = np.concatenate(s.ids)
    assert flat.min() >= 0 and flat.max() < 1000
    # Zipf s=1 over 1000 vertices: the hottest has 1/H(1000) = 13% of the mass
    assert np.bincount(flat).max() / len(flat) == pytest.approx(0.134, abs=0.02)


def test_onoff_keeps_the_mean_rate_and_the_silence():
    mix = dict(MIX, arrivals={"process": "onoff", "on_s": 0.5, "period_s": 2.0})
    s = openloop.make_schedule(2, mix, 1000, 20.0)
    assert len(s.due_s) == pytest.approx(8000, rel=0.06)
    assert np.all(s.due_s % 2.0 < 0.5)


def test_uniform_vertices():
    mix = dict(MIX, vertex={"dist": "uniform"})
    flat = np.concatenate(openloop.make_schedule(3, mix, 50, 10.0).ids)
    assert np.bincount(flat, minlength=50).min() > 0


class _Reply:
    def __init__(self, delay_s, fail=False):
        self._event = threading.Event()
        self._fail = fail
        threading.Timer(delay_s, self._event.set).start()

    def result(self, timeout):
        if not self._event.wait(timeout):
            raise TimeoutError
        if self._fail:
            raise RuntimeError("refused")


def test_latency_runs_from_the_due_time_and_lateness_is_reported():
    # three requests due at 0, 10 and 20 ms; the first submit stalls the
    # sender for 50 ms, so the second and third go out late. Each is
    # answered 5 ms after it is sent.
    schedule = openloop.Schedule(
        due_s=np.asarray([0.0, 0.010, 0.020]), ids=[np.asarray([1])] * 3
    )
    calls = []

    def submit(ids):
        if not calls:
            time.sleep(0.050)
        calls.append(ids)
        return _Reply(0.005)

    out = openloop.run_schedule(schedule, submit, timeout_s=2.0)
    lat = out.latency_ms(2000.0)
    late = out.late_ms
    assert out.ok.all()
    assert late[0] == pytest.approx(50.0, abs=10.0)  # sent stamps follow submit
    assert late[1] == pytest.approx(40.0, abs=10.0)
    assert late[2] == pytest.approx(30.0, abs=10.0)
    # from the due time: the stall is in the latency of those it delayed
    assert lat[1] == pytest.approx(45.0, abs=10.0)
    assert lat[2] == pytest.approx(35.0, abs=10.0)
    assert np.all(lat >= late)


def test_a_refused_request_counts_as_failed_and_as_the_timeout():
    schedule = openloop.Schedule(
        due_s=np.asarray([0.0, 0.001]), ids=[np.asarray([1]), np.asarray([2])]
    )
    replies = iter([_Reply(0.001), _Reply(0.001, fail=True)])
    out = openloop.run_schedule(schedule, lambda ids: next(replies), timeout_s=1.0)
    assert out.ok.tolist() == [True, False]
    assert out.latency_ms(1000.0)[1] == 1000.0
