"""``--rehearse`` of the cell PR 29 added, ``glm47flash_w256_train``, traced
and untraced, at the configuration's tiny ``rehearse`` widths on the CPU, read
from ``BENCHMARK.json`` (``BENCHMARK.proposed.json`` lags it since PR 26; the
next ``benchmark`` issue reconciles the two).  Each run is a process of its own,
as the driver's are.  Run by hand: ``python -m pytest benchmarks/tests -q`` (not
part of tier-1; about two minutes)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "glm47flash_w256_train"
# shares of a peak: a CPU has none in peaks.json, so a rehearsal leaves them out
OF_A_PEAK = ("mfu", "roofline")


def names(kind):
    return {m["name"] for m in BENCHMARK[kind] if CELL in m.get("workloads", [CELL])}


def run_cell(trace):
    return subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 29), "--seconds", "2", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=900)


def test_the_cell_is_in_the_benchmark_with_its_configuration_and_traffic():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ppo_glm47flash_ep8_bf16", "m1q_16x16", 1)
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == cell["config"])
    conf = json.loads((ROOT / entry["file"]).read_text())
    assert sorted(entry["reduced"]) == sorted(conf["reduced"])
    assert {"env_steps_per_s", "setup_s"} <= names("end_to_end")
    assert {"moe_router_device_ms", "moe_dispatch_device_ms", "moe_experts_device_ms",
            "moe_shared_device_ms", "policy_mfu", "attention_block_device_ms",
            "ffn_block_device_ms"} <= names("per_layer")


def test_a_kernels_roofline_share_takes_the_rows_the_traced_steps_really_routed(monkeypatch):
    """At the expected 12.5 % a run whose held experts got half of it read over
    100 % (PR 29, call 43): the readers take the counter the check's twin left,
    and nothing where none was left."""
    sys.path[:0] = [str(ROOT / "benchmarks")]
    try:
        import harness
        import run as bench_run
        rooflines = harness.load_module("rooflines", "mla_moe_decoder")
        cell = bench_run.load_cell(CELL, False)
    finally:
        del sys.path[0]
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    monkeypatch.setattr(rooflines, "device_peak", lambda: peak)
    expected = rooflines.kernel_least_seconds(cell, "grouped_matmul", peak)
    run = {"cell": cell, "counters": {"train_steps": 3},
           "trace": {"device_ops": [("grouped_matmul.7 (tpu_custom_call)", 3 * expected / 0.5)]}}
    monkeypatch.delattr(harness, "traced_counters", raising=False)
    assert rooflines.kernel_roofline_share(run, "grouped_matmul") is None
    monkeypatch.setattr(harness, "traced_counters", {"moe_held_share": 0.125}, raising=False)
    assert rooflines.kernel_roofline_share(run, "grouped_matmul") == pytest.approx(50.0)
    monkeypatch.setattr(harness, "traced_counters", {"moe_held_share": 0.0625}, raising=False)
    half = rooflines.kernel_roofline_share(run, "grouped_matmul")
    assert 25.0 < half < 35.0           # the rows halve; every expert's weights are still read once a call


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_line(trace):
    done = run_cell(trace)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(l) for l in done.stdout.strip().splitlines() if l.startswith("{")]
    line = lines[-1]
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"      # never a device number
    wanted = names("per_layer" if trace else "end_to_end")
    assert {n for n in wanted if not any(p in n for p in OF_A_PEAK)} == set(line["metrics"])
    check = next(l["note"]["check"] for l in lines if "check" in l.get("note", {}))
    assert check["kind"] == "reference_policy" and not check["over_limit"]
    # the step that was compared is the step that was timed: same program, same seed
    assert check["twin_params_max_abs_diff"] == check["twin_loss_max_abs_diff"] == 0.0
    assert check["same_rollout"] and check["minibatches"] == 4
    assert 0 < check["update_rel_l2"] < check["controls"]["first_minibatch_only"]["update_rel_l2"]
    # what the limits have to refuse goes through them as the program does, and is refused
    assert all(read["refused_by"] for read in check["controls"].values()), check["controls"]
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        scopes = next(l["note"]["scope_ms"]["scopes"] for l in lines
                      if "scope_ms" in l.get("note", {}))
        for part in ("moe_router", "moe_dispatch", "moe_experts", "moe_shared"):
            assert f"rollout/policy_act/{part}" in scopes
            assert f"update/loss/policy_forward/{part}" in scopes
