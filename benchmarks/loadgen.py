"""The open-loop load generator: one schedule from the seed, one sender.

A traffic file gives ``rate`` (requests a second), ``sessions`` and
``row_pool``.  The schedule is a Poisson process at that rate conditioned on
its count: ``round(rate * seconds)`` arrival times, uniform on the span and
sorted, so the inter-arrival times are exponential in the limit and EVERY
seed offers the same number of requests in the window, in another order.  Each request
belongs to a session drawn uniformly and carries a row of the pool.

Requests are sent whether or not earlier ones were answered (open loop).
A request's latency runs from the time it was DUE on the schedule, not from
when the sender got round to it, so a stall is charged to every request it
delays; how late the sender ran is reported beside it.

``clock`` and ``sleep`` are arguments so that the arithmetic can be tested
on a fake clock (tests/test_loadgen.py).
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Schedule:
    due_s: np.ndarray      # seconds from the start of the span, sorted
    session: np.ndarray    # session index of each request
    row: np.ndarray        # index into the pool of observation rows


def make_schedule(seed: int, rate: float, spans_s, sessions: int,
                  row_pool: int) -> Schedule:
    """``spans_s`` are consecutive spans (a lead-in, then the window): each
    gets exactly ``round(rate * its length)`` arrivals."""
    rng = np.random.default_rng(seed)
    due, begin = [], 0.0
    for length in spans_s:
        due.append(np.sort(rng.uniform(begin, begin + length, int(round(rate * length)))))
        begin += length
    due = np.concatenate(due)
    return Schedule(due_s=due, session=rng.integers(0, sessions, len(due)),
                    row=rng.integers(0, row_pool, len(due)))


@dataclass
class Sent:
    """What the sender and the futures' callbacks write, one slot a request
    (NaN = never happened)."""

    due: np.ndarray                 # absolute clock time each request was due
    sent: np.ndarray
    done: np.ndarray                # resolve time of an answered request
    error: list = field(default_factory=list)   # (index, exception name)
    refused: int = 0                # submit() itself raised


def send(schedule: Schedule, submit, *, start: float, clock=time.perf_counter,
         sleep=time.sleep, span=None, spin_below_s: float = 2e-4) -> Sent:
    """Send every request of ``schedule`` at ``start + due_s`` through
    ``submit(i) -> Future``; returns when the last one is sent.  ``span`` is
    a context-manager factory (name -> span) for the profiler's host spans.
    Waits longer than ``spin_below_s`` sleep (and release the interpreter);
    shorter ones yield and look again."""
    span = span or (lambda name: contextlib.nullcontext())
    n = len(schedule.due_s)
    out = Sent(due=start + schedule.due_s, sent=np.full(n, np.nan),
               done=np.full(n, np.nan))
    done, error = out.done, out.error

    def on_done(i):
        def callback(future):
            if future.exception() is None:
                done[i] = clock()
            else:
                error.append((i, type(future.exception()).__name__))
        return callback

    for i in range(n):
        due = out.due[i]
        wait = due - clock()
        if wait > 0:
            with span("bench.wait_next_arrival"):
                _wait(due, clock, sleep, spin_below_s)
        out.sent[i] = clock()
        try:
            with span("bench.submit"):
                future = submit(i)
        except Exception as exc:  # shed at the door: counted, never forgiven
            out.refused += 1
            error.append((i, type(exc).__name__))
            continue
        future.add_done_callback(on_done(i))
    return out


def _wait(due, clock, sleep, spin_below_s):
    while True:
        wait = due - clock()
        if wait <= 0:
            return
        sleep(wait if wait > spin_below_s else 0)


def wait_answers(sent: Sent, timeout_s: float, clock=time.perf_counter,
                 sleep=time.sleep) -> bool:
    """Wait until every request sent was answered or failed, at most
    ``timeout_s``; False if some are still out."""
    deadline = clock() + timeout_s
    while int(np.isnan(sent.done).sum()) > len(sent.error):
        if clock() >= deadline:
            return False
        sleep(0.001)
    return True


def backlog(sent: Sent, t: float) -> int:
    """Requests due before ``t`` and not answered by ``t``."""
    return int(np.sum((sent.due < t) & ~(sent.done < t)))


def summarise(sent: Sent, t0: float, t1: float, t_drained: float) -> dict:
    """The window ``[t0, t1)`` by due time.  A request that was never
    answered (shed, failed, or still out when the drain ended at
    ``t_drained``) counts as failed, and as a latency running to the end of
    the drain, so that it misses any limit."""
    inside = (sent.due >= t0) & (sent.due < t1)
    due, done, was_sent = sent.due[inside], sent.done[inside], sent.sent[inside]
    answered = ~np.isnan(done)
    latency = np.where(answered, done, t_drained) - due
    late = was_sent[~np.isnan(was_sent)] - due[~np.isnan(was_sent)]
    resolved_inside = int(np.sum((sent.done >= t0) & (sent.done < t1)))

    def pct(values, q):
        return float(np.percentile(values, q)) if len(values) else float("nan")

    return {
        "attempted": int(inside.sum()), "failed": int((~answered).sum()),
        "resolved_inside": resolved_inside,
        "decisions_per_s": resolved_inside / (t1 - t0),
        "latency_ms": {q: 1e3 * pct(latency, q) for q in (50, 95, 99)},
        "generator_late_ms": {q: 1e3 * pct(late, q) for q in (50, 95, 99)},
        "backlog_mid": backlog(sent, (t0 + t1) / 2), "backlog_end": backlog(sent, t1),
    }
