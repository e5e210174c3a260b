"""test_rehearse.py's pattern for the per-layer device times (PR 26): one
``--rehearse --trace 1`` run of each ACCEPTED cell (``BENCHMARK.json``, which
holds the new metrics; the proposed file that test_rehearse.py reads is a
benchmark file of PR 24 and stays as it was) prints every metric the cell
lists and the ``scope_ms`` note line, and the numbers add up.  Each run is a
process of its own, as the driver's are.
Run by hand: ``python -m pytest benchmarks/tests -q`` (not part of tier-1)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
SCOPE_METRICS = {m["name"] for m in BENCHMARK["per_layer"]
                 if m["name"].endswith("_device_ms") or m["name"] == "scope_coverage"}


def names(cell):
    return {m["name"] for m in BENCHMARK["per_layer"] if cell in m.get("workloads", [cell])}


@pytest.fixture(scope="module", params=CELLS)
def rehearsal(request):
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload", request.param,
         "--seed", str(2**31 + 11), "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    notes = [line["note"]["scope_ms"] for line in lines
             if "scope_ms" in line.get("note", {})]
    return request.param, lines[-1], notes


def test_every_metric_of_the_cell_is_on_the_line(rehearsal):
    cell, line, _notes = rehearsal
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == names(cell)
    assert len(names(cell) & SCOPE_METRICS) in (11, 13)  # the two blocks: one cell
    for name in names(cell) & SCOPE_METRICS:
        value = line["metrics"][name]
        assert isinstance(value["value"], float) and value["value"] >= 0, name
        assert value["unit"] == ("%" if name == "scope_coverage" else "ms")


def test_the_table_is_printed_once_and_adds_up(rehearsal):
    _cell, line, notes = rehearsal
    assert len(notes) == 1                       # thirteen readers, one table
    note, metrics = notes[0], {k: v["value"] for k, v in line["metrics"].items()}
    whole = (metrics["rollout_device_ms"] + metrics["update_device_ms"]
             + note["outside_phases_ms"])
    assert whole == pytest.approx(note["busy_ms_per_step"], rel=1e-9)
    assert note["scopes"]["rollout"]["with_children"] == metrics["rollout_device_ms"]
    under_rollout = sum(metrics[name] for name in (
        "policy_act_device_ms", "tape_read_device_ms", "env_dynamics_device_ms",
        "obs_encode_device_ms"))
    assert 0 < under_rollout <= metrics["rollout_device_ms"]
    under_update = sum(metrics[name] for name in (
        "update_prepare_device_ms", "loss_forward_device_ms",
        "loss_backward_device_ms", "optimizer_device_ms"))
    assert 0 < under_update <= metrics["update_device_ms"]
    assert 0 < metrics["scope_coverage"] <= 100
    assert len(note["largest_ops"]) == 3
