#!/usr/bin/env python3
"""Find the serving knee once, on the chip (the builder's tool, not a cell).

    python benchmarks/sweep_knee.py --workload <serving cell> [--step_s 5] [--start 2000]

One process, one booted stack.  Steps of ``step_s`` seconds of the cell's
open loop at a fixed rate: the rate doubles until a step is not sustained,
then the gap is bisected ``bisections`` times.  Each step draws its own
arrival times from its own seed (``Stack.offer``), also where the cell's runs
replay one set of arrivals: a knee read on one pattern would read that
pattern's last burst.  A step is SUSTAINED when
  - nothing was shed or failed;
  - the generator kept to its schedule (95 % of requests sent within 10 ms of
    their due time);
  - the backlog did not grow: its mean over ``INSTANTS`` instants of the
    step's second half is at most a quarter over its mean in the first half,
    plus one request (one in service at any load is no growth; a backlog that
    grows steadily from nothing doubles);
  - where the traffic file states ``knee_p95_limit_ms``, the step's p95
    latency is within it: a server that batches keeps any backlog bounded by
    filling its largest bucket from a standing queue, so without a latency
    limit its "knee" is the rate at which the tail is seconds long.
The knee is the highest sustained rate; the cell's traffic file then carries a
fixed share of it (its ``what`` says which; ``four_fifths`` and ``half`` are
printed).  The stack is the one the cell's runner boots
(``runners/<runner>.py``'s ``Stack``).  Prints one JSON line a step and the
table's summary last.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent)]

INSTANTS = 50


def mean_backlog(sent, start: float, end: float) -> float:
    """The backlog's mean over ``INSTANTS`` evenly spaced instants of ``[start, end)``."""
    import numpy as np

    import loadgen

    return float(np.mean([loadgen.backlog(sent, t)
                          for t in np.linspace(start, end, INSTANTS, endpoint=False)]))


def sustained(row: dict, limit_ms) -> bool:
    """The step's verdict (the module's docstring)."""
    return bool(row["failed"] == 0 and row["shed"] == 0
                and row["generator_late_ms"][95] <= 10.0
                and row["backlog_late"] <= 1.25 * row["backlog_early"] + 1.0
                and (limit_ms is None or row["latency_ms"][95] <= limit_ms))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--step_s", type=float, default=5.0)
    ap.add_argument("--start", type=float, default=2000.0)
    ap.add_argument("--bisections", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--benchmark", default="BENCHMARK.json")
    args = ap.parse_args(argv)

    import importlib
    import time

    import run as entry

    cell = entry.load_cell(args.workload, args.rehearse, args.benchmark)
    import harness
    import loadgen

    # the serving stack of the cell's own runner (``runners/<runner>.py``)
    Stack = importlib.import_module(f"runners.{cell['config']['runner']}").Stack

    devices, _cache_dir = harness.open_devices(1, args.rehearse)
    if devices is None:
        return 2
    ctx = harness.Context(cell=cell, seed=args.seed, seconds=args.step_s, trace=False,
                          rehearse=args.rehearse, t0=time.perf_counter(), devices=devices[:1])
    stack = Stack(ctx)
    stack.seed_slots()
    table = []

    limit_ms = cell["traffic"].get("knee_p95_limit_ms")

    def step(rate: float) -> bool:
        sent, t0, t1, t_end = stack.offer(
            harness.seed31(args.seed, len(table)), rate, 0.5, args.step_s, 30.0)
        row = loadgen.summarise(sent, t0, t1, t_end)
        half = (t0 + t1) / 2
        row.update(rate=rate, shed=int(stack.batcher.shed_count),
                   backlog_early=mean_backlog(sent, t0, half),
                   backlog_late=mean_backlog(sent, half, t1), p95_limit_ms=limit_ms)
        row["sustained"] = sustained(row, limit_ms)
        table.append(row)
        print(json.dumps(row), flush=True)
        return row["sustained"]

    try:
        rate = args.start
        while step(rate) and rate < 1e6:
            rate *= 2
        low, high = (rate / 2 if len(table) > 1 else 0.0), rate
        for _ in range(args.bisections):
            mid = (low + high) / 2
            low, high = (mid, high) if step(mid) else (low, mid)
    finally:
        stack.batcher.close(timeout=30)
    print(json.dumps({"knee": low, "first_not_sustained": high,
                      "four_fifths": 0.8 * low, "half": 0.5 * low,
                      "device": harness.device_report(devices, 1)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
