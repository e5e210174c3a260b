#!/usr/bin/env python3
"""Find the serving knee once, on the chip (the builder's tool, not a cell).

    python benchmarks/sweep_knee.py --workload <serving cell> [--step_s 5] [--start 2000]

One process, one booted stack.  Steps of ``step_s`` seconds of the cell's
open loop at a fixed rate: the rate doubles until a step is not sustained,
then the gap is bisected twice.  A step is SUSTAINED when nothing was shed
or failed, the generator kept to its schedule (95 % of requests sent within
10 ms of their due time) and the backlog at its end is no larger than at its
middle — where "no larger" allows for noise: at most a quarter more, plus
5 ms of arrivals (a backlog that grows steadily from nothing doubles).  The knee is the highest sustained rate; the
cell's traffic file then carries four fifths of it.  Prints one JSON line a
step and the table's summary last.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent)]


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

    import time

    import run as entry

    cell = entry.load_cell(args.workload, args.rehearse, args.benchmark)
    import harness
    import loadgen
    from runners.serve import Stack

    devices, _cache_dir = harness.open_devices(1, args.rehearse)
    if devices is None:
        return 2
    ctx = harness.Context(cell=cell, seed=args.seed, seconds=args.step_s, trace=False,
                          rehearse=args.rehearse, t0=time.perf_counter(), devices=devices[:1])
    stack = Stack(ctx)
    stack.seed_slots()
    table = []

    def step(rate: float) -> bool:
        sent, t0, t1, t_end = stack.offer(
            harness.seed31(args.seed, len(table)), rate, 0.5, args.step_s, 30.0)
        row = loadgen.summarise(sent, t0, t1, t_end)
        row.update(rate=rate, shed=int(stack.batcher.shed_count), sustained=bool(
            row["failed"] == 0 and row["generator_late_ms"][95] <= 10.0
            and row["backlog_end"] <= 1.25 * row["backlog_mid"] + 0.005 * rate))
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
                      "four_fifths": 0.8 * low,
                      "device": harness.device_report(devices, 1)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
