"""The Poisson schedule and the due-time latency arithmetic, on a fake clock.
Run by hand: ``python -m pytest benchmarks/tests -q`` (not part of tier-1)."""
import sys
from concurrent.futures import Future
from pathlib import Path

import numpy as np

sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

import loadgen  # noqa: E402


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now

    def sleep(self, seconds):
        self.now += max(seconds, 1e-4)


def test_schedule_has_the_same_count_for_every_seed_and_poisson_gaps():
    a = loadgen.make_schedule(1, 1000.0, (0.5, 4.0), 64, 16)
    b = loadgen.make_schedule(2**31 + 7, 1000.0, (0.5, 4.0), 64, 16)
    for s in (a, b):
        assert len(s.due_s) == 4500
        assert int((s.due_s < 0.5).sum()) == 500          # the lead-in's share
        assert np.all(np.diff(s.due_s) >= 0) and s.due_s[-1] < 4.5
        assert s.session.min() >= 0 and s.session.max() < 64
    assert not np.array_equal(a.due_s, b.due_s)
    gaps = np.diff(a.due_s[500:])
    # exponential gaps: mean 1/rate, coefficient of variation 1
    assert abs(gaps.mean() - 1e-3) < 1e-4
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1
    same = loadgen.make_schedule(1, 1000.0, (0.5, 4.0), 64, 16)
    assert np.array_equal(a.due_s, same.due_s)


def test_latency_runs_from_the_due_time_not_from_the_send():
    clock = FakeClock()
    schedule = loadgen.Schedule(due_s=np.array([0.0, 0.010, 0.020, 0.030]),
                                session=np.zeros(4, int), row=np.zeros(4, int))
    futures = []

    def submit(i):
        if i == 1:
            clock.now += 0.050          # the sender stalls for 50 ms here
        if i == 3:
            raise RuntimeError("shed at the door")
        futures.append(Future())
        return futures[-1]

    sent = loadgen.send(schedule, submit, start=100.0, clock=clock, sleep=clock.sleep)
    assert sent.refused == 1 and sent.error == [(3, "RuntimeError")]
    # request 2 was due at 100.020 but sent after the stall
    assert sent.sent[2] >= 100.060 - 1e-9
    clock.now = 100.100
    futures[0].set_result("a")
    futures[1].set_result("b")
    clock.now = 100.200
    futures[2].set_exception(ValueError("deadline"))
    assert loadgen.wait_answers(sent, 1.0, clock=clock, sleep=clock.sleep)
    out = loadgen.summarise(sent, 100.0, 100.040, t_drained=101.0)
    assert out["attempted"] == 4 and out["failed"] == 2
    latency = np.where(np.isnan(sent.done), 101.0, sent.done) - sent.due
    assert np.allclose(latency, [0.100, 0.090, 0.980, 0.970])
    assert abs(out["latency_ms"][50] - 1e3 * np.percentile(latency, 50)) < 1e-6
    # lateness of the sender: request 2 went out 40 ms after it was due
    assert out["generator_late_ms"][99] > 35.0
    assert out["resolved_inside"] == 0
    assert loadgen.backlog(sent, 100.050) == 4 and loadgen.backlog(sent, 100.150) == 2


def test_window_counts_only_requests_due_inside_it():
    clock = FakeClock()
    schedule = loadgen.make_schedule(3, 100.0, (1.0, 2.0), 8, 4)

    def submit(i):
        f = Future()
        f.set_result(i)                  # answered at once
        return f

    sent = loadgen.send(schedule, submit, start=100.0, clock=clock, sleep=clock.sleep)
    out = loadgen.summarise(sent, 101.0, 103.0, t_drained=104.0)
    assert out["attempted"] == 200 and out["failed"] == 0
    assert out["backlog_end"] == 0
    assert 95.0 <= out["decisions_per_s"] <= 100.0
