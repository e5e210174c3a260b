#!/usr/bin/env python3
"""One cell of BENCHMARK.json, run once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

ONE PROCESS, no child (a chip belongs to one process).  The cell's entry
in BENCHMARK.json names a configuration and a traffic mix; both are data
files (``configs/<name>.json``, ``traffic/<name>.json``).  The
configuration's ``runner`` key picks ``runners/<runner>.py``; with
``--trace 1`` every per-layer metric of the cell is read by
``layer_metrics/<metric>.py``.  This file holds no cell's, configuration's,
mix's or metric's name: adding one of them adds a file or an entry and
edits nothing here (see README.md).

No TPU, or fewer chips than the cell asks for -> exit 2 before any work,
nothing printed on stdout.  ``--rehearse`` is the builder's CPU mode: the
files' ``rehearse`` overrides (tiny sizes, Pallas interpreted) and
``device.platform`` says ``cpu``; such a line is never a device number.

The LAST line of stdout is the contract's object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``); everything else goes on earlier lines.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse
import importlib
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT)]


def merged(base: dict, over: dict) -> dict:
    """``over`` laid on ``base``, nested objects merged key by key."""
    out = dict(base)
    for key, value in over.items():
        both = isinstance(value, dict) and isinstance(out.get(key), dict)
        out[key] = merged(out[key], value) if both else value
    return out


def load_cell(name: str, rehearse: bool, benchmark: str = "BENCHMARK.json") -> dict:
    """The cell's entry with its configuration and traffic files read in,
    and the metric entries of the benchmark file that apply to it."""
    bench = json.loads((ROOT / benchmark).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in {benchmark}: {sorted(cells)}")
    cell = dict(cells[name])
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    files = {
        "config": ROOT / entry["file"],
        "traffic": BENCH / "traffic" / f"{cell['traffic']}.json",
    }
    for key, path in files.items():
        data = json.loads(path.read_text())
        if rehearse:
            data = merged(data, data.get("rehearse", {}))
        data.pop("rehearse", None)
        cell[f"{key}_name"], cell[key] = cell[key], data

    def applies(metric):
        return name in metric.get("workloads", [name])

    cell["end_to_end"] = [m for m in bench["end_to_end"] if applies(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if applies(m)]
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the files' tiny `rehearse` sizes")
    ap.add_argument("--benchmark", default="BENCHMARK.json",
                    help="another file of the same form, relative to the repo's root "
                         "(cells that are proposed and not yet accepted)")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, args.rehearse, args.benchmark)
    import harness

    devices, cache_dir = harness.open_devices(cell["chips"], args.rehearse)
    if devices is None:
        return 2
    import jax

    harness.note(cell=cell["name"], seed=args.seed, seconds=args.seconds,
                 trace=args.trace, rehearse=args.rehearse,
                 compile_cache_dir=cache_dir, jax=jax.__version__)

    runner = importlib.import_module(f"runners.{cell['config']['runner']}")
    run = runner.run(harness.Context(
        cell=cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        rehearse=args.rehearse, t0=T0, devices=devices[:cell["chips"]],
    ))

    metrics = {}
    if args.trace:
        for entry in cell["per_layer"]:
            value = harness.load_module("layer_metrics", entry["name"]).read(run)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    else:
        for entry in cell["end_to_end"]:
            if entry["name"] in run["end_to_end"]:
                metrics[entry["name"]] = {
                    "value": run["end_to_end"][entry["name"]], "unit": entry["unit"]}

    device = harness.device_report(devices, cell["chips"])
    line = {"correct": bool(run["correct"]), "attempted": int(run["attempted"]),
            "failed": int(run["failed"]), "metrics": metrics, "device": device}
    if args.trace and run.get("trace"):
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = {"device_ops": run["trace"]["device_ops"][:10],
                             "idle_gaps": run["trace"]["idle_gaps"][:10]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
