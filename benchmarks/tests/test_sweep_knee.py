"""The knee sweep's verdict on a step (``sweep_knee.sustained``), on requests
made up by hand.  Run by hand: ``python -m pytest benchmarks/tests -q`` (not
part of tier-1)."""
import sys
from pathlib import Path

import numpy as np

sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

import loadgen  # noqa: E402
import sweep_knee  # noqa: E402


def step(due, done, limit_ms=None):
    """The row ``sweep_knee`` judges, for a window of [0, 20) s."""
    sent = loadgen.Sent(due=np.asarray(due, float), sent=np.asarray(due, float),
                        done=np.asarray(done, float))
    row = loadgen.summarise(sent, 0.0, 20.0, 40.0)
    row.update(shed=0, backlog_early=sweep_knee.mean_backlog(sent, 0.0, 10.0),
               backlog_late=sweep_knee.mean_backlog(sent, 10.0, 20.0))
    return row, sweep_knee.sustained(row, limit_ms)


def test_a_burst_at_the_end_of_a_step_is_no_growing_backlog():
    # 10 requests a second answered in 80 ms, and a last burst of eight at 19.9 s
    # that the two-instant test read as a backlog grown from 0 to 8
    due = np.concatenate([np.arange(0.0, 19.9, 0.1), np.full(8, 19.9)])
    done = np.concatenate([np.arange(0.0, 19.9, 0.1) + 0.08, 19.9 + 0.08 * np.arange(1, 9)])
    row, ok = step(due, done)
    assert row["backlog_end"] >= 7 and ok


def test_a_backlog_that_grows_through_the_step_is_not_sustained():
    # 20 requests a second served at 15 a second: the queue grows all through
    due = np.arange(0.0, 20.0, 0.05)
    done = np.arange(1, len(due) + 1) / 15.0
    row, ok = step(due, done)
    assert row["backlog_late"] > 1.25 * row["backlog_early"] + 1 and not ok


def test_a_standing_queue_within_the_latency_limit_is_sustained_and_over_it_is_not():
    # a standing queue of about ten requests: no growth, latency about a second
    due = np.arange(0.0, 20.0, 0.1)
    done = due + 1.0
    assert step(due, done)[1]
    assert step(due, done, limit_ms=1500.0)[1]
    assert not step(due, done, limit_ms=250.0)[1]
