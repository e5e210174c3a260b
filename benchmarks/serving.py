"""The open-loop window of the serving runners (``runners/serve.py``,
``runners/serve_window.py``), shared: each runner brings its ``Stack`` and its
check, and this module runs the window, summarises it and gives the terms of
``correct`` every serving cell has.

Window: ``send(span)`` offers the runner's schedule (a lead-in of the same
traffic, not judged, then the window) from THIS thread and waits for what is
still out; ``setup_s`` ends where the window starts.  Traced, the same under
the profiler.  Latencies run from due times (``loadgen.summarise``).
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass

import numpy as np

import harness
import loadgen


@dataclass
class Window:
    sent: loadgen.Sent
    t0: float              # the window's start and end on the host clock
    t1: float
    t_end: float           # end of the wait for answers
    summary: dict
    end_to_end: dict
    trace: dict
    compiled_inside: int   # programs built (or fetched) inside the window


def open_loop(ctx: harness.Context, compiles: harness.CompileCounter, lead_in: float,
              send) -> Window:
    """Run ``send(span) -> (sent, t0, t1, t_end)``, traced where the run is."""
    import jax

    trace, end_to_end = {}, {}
    window = (harness.traced_window(ctx.cell["name"], trace) if ctx.trace
              else contextlib.nullcontext())
    before = compiles.count
    with window:
        end_to_end["setup_s"] = time.perf_counter() + lead_in - ctx.t0
        sent, t0, t1, t_end = send(jax.profiler.TraceAnnotation if ctx.trace else None)
    summary = loadgen.summarise(sent, t0, t1, t_end)
    end_to_end["decision_p95_ms"] = summary["latency_ms"][95]
    end_to_end["decisions_per_s"] = summary["decisions_per_s"]
    return Window(sent, t0, t1, t_end, summary, end_to_end, trace, compiles.count - before)


def counters(engine, batcher, win: Window) -> dict:
    return {"late_compiles": int(engine.late_compiles), "batch_mode": engine.batch_mode,
            "dispatches": int(batcher.dispatches),
            "compiled_inside_window": win.compiled_inside, "shed": int(batcher.shed_count),
            "refused": win.sent.refused, "errors": len(win.sent.error)}


def served_soundly(counts: dict, conf: dict, win: Window) -> bool:
    """What every serving cell's ``correct`` holds beside its check: nothing
    compiled inside the window or late, the batch mode the configuration
    expects, and requests attempted."""
    return (counts["late_compiles"] == 0 and counts["compiled_inside_window"] == 0
            and counts["batch_mode"] == conf["expect"]["batch_mode"]
            and win.summary["attempted"] > 0)


def dispatch_stats(records, t0: float) -> dict:
    """Where the window's longest waits lay, from the batcher's records of the
    requests due in it: the longest dispatch (engine call to answers), the
    longest staging (batching window opened to engine call) and the longest
    time the worker took to open a batching window while a request waited and
    no dispatch was running (``pickup_lag``); each in ms with its offset from
    the window's start in s."""
    if not records:
        return {}
    by_dispatch = {}
    for r in records:
        by_dispatch.setdefault(r.t_dispatch, []).append(r)
    batches = sorted(by_dispatch.values(), key=lambda b: b[0].t_dispatch)
    done = [b[0].t_done for b in batches]
    longest = {"dispatch": (0.0, None), "staging": (0.0, None), "pickup_lag": (0.0, None)}

    def keep(name, ms, at, **more):
        if ms > longest[name][0]:
            longest[name] = (ms, {"ms": ms, "at_s": at - t0, **more})

    for i, batch in enumerate(batches):
        first = batch[0]
        keep("dispatch", 1e3 * (first.t_done - first.t_dispatch), first.t_dispatch,
             bucket=first.bucket, batch=first.batch_size)
        keep("staging", 1e3 * (first.t_dispatch - first.t_pickup), first.t_pickup)
        free = max([min(r.t_enqueue for r in batch)] + ([done[i - 1]] if i else []))
        keep("pickup_lag", 1e3 * (first.t_pickup - free), free)
    return {name: read for name, (_, read) in longest.items() if read}


def result(correct: bool, win: Window, counts: dict, records, cell: dict, **more) -> dict:
    return {"correct": bool(correct), "attempted": win.summary["attempted"],
            "failed": win.summary["failed"], "end_to_end": win.end_to_end,
            "trace": win.trace, "spans": {"generator_late_ms": win.summary["generator_late_ms"]},
            "counters": counts, "records": records, "cell": cell, **more}


def bucket_rows(sent: loadgen.Sent, records):
    """(batch size, row in the batch) of every request sent, from the
    batcher's records, which it keeps one a request in the order it took
    them: first in, first out, so the k-th record is the k-th request sent.
    None where the records do not bear that out (a count that differs, or a
    record enqueued outside its request's turn)."""
    n = len(sent.sent)
    if len(records) != n or np.isnan(sent.sent).any():
        return None
    enqueued = np.asarray([r.t_enqueue for r in records])
    if not (np.all(enqueued >= sent.sent) and np.all(enqueued[:-1] <= sent.sent[1:])):
        return None
    dispatched = np.asarray([r.t_dispatch for r in records])
    row = np.zeros(n, np.int64)
    for k in range(1, n):
        row[k] = row[k - 1] + 1 if dispatched[k] == dispatched[k - 1] else 0
    return np.asarray([r.batch_size for r in records]), row
