"""One ``--rehearse`` run of each runner end to end at tiny sizes on the CPU,
the train runner also on four virtual CPU devices with a ``mesh`` in its
traffic (what the four-chip cell will add as data), and the refusal to run
without a TPU.  Each run is a process of its own, as the driver's are.
Run by hand: ``python -m pytest benchmarks/tests -q`` (not part of tier-1;
about two minutes)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
# the accepted cells and those proposed for a later PR (a superset)
PROPOSED = "benchmarks/BENCHMARK.proposed.json"
BENCHMARK = json.loads((ROOT / PROPOSED).read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
TRAIN_CELL = next(
    w["name"] for w in BENCHMARK["workloads"]
    if json.loads((ROOT / next(c["file"] for c in BENCHMARK["configs"]
                               if c["name"] == w["config"])).read_text())["runner"] == "train")


def run_cell(*args, env=None, code=None):
    base = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    command = [sys.executable, "-c", code] if code else \
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--benchmark", PROPOSED, *args]
    return subprocess.run(command, cwd=ROOT, env=base, capture_output=True,
                          text=True, timeout=900)


def names(kind, cell):
    return {m["name"] for m in BENCHMARK[kind] if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contract_line(cell, trace):
    done = run_cell("--workload", cell, "--seed", str(2**31 + 11), "--seconds", "2",
                    "--trace", str(trace), "--rehearse")
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"      # never a device number
    kind = "per_layer" if trace else "end_to_end"
    wanted = names(kind, cell)
    if trace:
        # a CPU has no peak in peaks.json: the shares of one are left out
        wanted = {n for n in wanted if "mfu" not in n and not n.endswith("_roofline")}
    assert set(line["metrics"]) == wanted
    for value in line["metrics"].values():
        assert isinstance(value["value"], float) and value["unit"]
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert line["breakdown"]["device_ops"] and line["breakdown"]["idle_gaps"]


def test_every_accepted_cell_is_among_the_proposed():
    accepted = json.loads((ROOT / "BENCHMARK.json").read_text())
    proposed = {w["name"]: w for w in BENCHMARK["workloads"]}
    for cell in accepted["workloads"]:
        assert proposed[cell["name"]] == cell


def test_no_tpu_and_no_rehearse_is_refused_before_any_work():
    done = run_cell("--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert not [l for l in done.stdout.splitlines() if l.startswith('{"correct"')]


def test_train_runner_takes_a_mesh_from_the_traffic_file():
    code = f"""
import json, sys, time
sys.path[:0] = [{str(ROOT / 'benchmarks')!r}, {str(ROOT)!r}]
import jax, harness, run
from runners import train
cell = run.load_cell({TRAIN_CELL!r}, True, {PROPOSED!r})
cell["traffic"]["mesh"] = {{"data": 4}}
cell["chips"] = 4
out = train.run(harness.Context(cell=cell, seed=3, seconds=1.0, trace=False,
    rehearse=True, t0=time.perf_counter(), devices=jax.devices()[:4]))
print(json.dumps({{"correct": out["correct"], "steps": out["attempted"],
                  "devices": len(jax.devices())}}))
"""
    done = run_cell(code=code, env={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert done.returncode == 0, done.stderr[-3000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert out == {"correct": True, "steps": out["steps"], "devices": 4} and out["steps"] > 0
