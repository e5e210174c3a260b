"""Tier-1 smoke for the benchmark contract: ``python bench.py --quick``
must exit 0 on CPU and end its stdout with the single JSON line
(metric / value / vs_baseline) that downstream dashboards parse
unconditionally (docs/performance.md, Benchmark contract)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "tools"))

from check_bench_contract import validate_record  # noqa: E402


def test_bench_quick_prints_single_json_line_contract():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # share the suite's persistent compile cache so the smoke pays the
    # big PPO program's compile at most once across CI runs
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--quick"],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=480,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    assert lines, f"bench printed nothing to stdout: {proc.stderr[-2000:]}"
    payload = json.loads(lines[-1])  # the contract: final line IS the JSON
    # committed key-set contract (tools/bench_contract_schema.json) —
    # includes the r7 telemetry keys mfu_analytic / device_memory_bytes
    problems = validate_record(payload)
    assert not problems, (problems, payload)
    for key in ("metric", "value", "vs_baseline"):
        assert key in payload, (key, payload)
    assert payload["metric"] == "ppo_env_steps_per_sec_per_chip"
    assert payload["value"] > 0
    assert payload["supersteps"] == 1
    assert payload["dispatch_overhead_frac"] is None  # K=1: no comparison
    # r6 phase attribution: the rollout/update split keys must be in
    # every record (they attribute the cycle)
    for key in ("rollout_ms", "update_ms"):
        assert key in payload, (key, payload)
        assert payload[key] is not None and payload[key] > 0, (key, payload)
    # r10 overlap accounting: overlap savings need a K>1 superstep to
    # measure against, so K=1 reports null — never a fabricated number
    assert "overlap_ms_saved" in payload, payload
    assert payload["overlap_ms_saved"] is None
    # the update phase's FLOP share comes off the same XLA cost
    # analysis as rollout_ms/update_ms and is a real fraction on CPU
    assert "update_gemm_frac" in payload, payload
    if payload["update_gemm_frac"] is not None:
        assert 0.0 < payload["update_gemm_frac"] <= 1.0, payload


@pytest.mark.slow
def test_multichip_bench_quick_emits_schema_valid_scaling_row():
    """tools/multichip_bench.py --quick on the 8-virtual-device CPU
    mesh: the final stdout line is a schema-valid multichip record with
    real aggregate/scaling numbers — the row the MULTICHIP harness
    emits (same build_record code path).  Slow-marked: the subprocess
    compiles its own sharded programs (~40s); the tier-1 schema gate on
    multichip rows is the MULTICHIP harness's own validate_record
    assert (__graft_entry__.py)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "multichip_bench.py"),
         "--quick"],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=480,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    payload = json.loads(lines[-1])
    problems = validate_record(payload)
    assert not problems, (problems, payload)
    assert payload["metric"] == "multichip_env_steps_per_sec"
    assert payload["aggregate_steps_per_sec"] > 0
    assert payload["single_device_steps_per_sec"] > 0
    assert payload["scaling_efficiency"] > 0
    assert payload["n_devices"] == 8
    assert payload["mesh_shape"] == {"data": 8}
    # off-TPU the MFU is null, never fabricated, and no record quotes a
    # single-chip anchor from another day's code
    assert "vs_single_chip_anchor" not in payload
    assert payload["mfu_analytic"] is None


def test_lob_bench_quick_emits_schema_valid_fills_row():
    """``bench.py --lob --quick`` (PR 8): the final stdout line is a
    schema-valid ``lob_fills_per_sec`` record from a real vmapped
    depth sweep — the row ROADMAP item 3 and docs/lob.md quote."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--lob", "--quick"],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=480,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    payload = json.loads(lines[-1])
    problems = validate_record(payload)
    assert not problems, (problems, payload)
    assert payload["metric"] == "lob_fills_per_sec"
    assert payload["value"] > 0
    assert payload["msgs_per_sec"] > 0
    assert payload["books"] == 256  # --quick shapes
    assert payload["queue_slots"] == 4
    # the sweep holds one row per swept depth, each with real numbers
    assert set(payload["depth_sweep"]) == {"8", "24"}
    for row in payload["depth_sweep"].values():
        assert row["fills_per_sec"] > 0
        assert row["fill_events_per_dispatch"] > 0
    # headline row == the venue-default depth-24 sweep entry
    assert payload["depth_levels"] == 24
    assert payload["value"] == payload["depth_sweep"]["24"]["fills_per_sec"]
    # r10: every bench row carries the analytic-MFU key block (shared
    # emitter bench_util.emit_bench_record) — null on CPU / for integer
    # matching, but the KEYS are pinned so dashboards parse one schema
    for key in ("analytic_flops_per_step", "hw_flops_peak",
                "mfu_analytic", "device_memory_bytes"):
        assert key in payload, (key, payload)
    assert payload["mfu_analytic"] is None  # no FLOP model for matching
    assert payload["lob_match_kernel"] == "off"  # oracle is the default


def test_scengen_bench_quick_emits_schema_valid_bars_row():
    """``bench.py --scengen --quick`` (PR 9): the final stdout line is a
    schema-valid ``scengen_bars_per_sec`` record from a real generation
    sweep over two presets — the row docs/scenarios.md quotes."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--scengen", "--quick"],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=480,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    payload = json.loads(lines[-1])
    problems = validate_record(payload)
    assert not problems, (problems, payload)
    assert payload["metric"] == "scengen_bars_per_sec"
    assert payload["value"] > 0
    assert payload["n_bars"] == 4096 and payload["n_assets"] == 1  # --quick
    # headline row == the first swept preset's entry
    assert payload["preset"] == "regime_mix"
    assert set(payload["preset_sweep"]) == {"regime_mix", "flash_crash"}
    for row in payload["preset_sweep"].values():
        assert row["bars_per_sec"] > 0 and row["gen_ms"] > 0
    assert payload["value"] == \
        payload["preset_sweep"]["regime_mix"]["bars_per_sec"]
    # r10: the shared emitter's analytic-MFU key block (null on CPU)
    for key in ("analytic_flops_per_step", "hw_flops_peak",
                "mfu_analytic", "device_memory_bytes"):
        assert key in payload, (key, payload)
    assert payload["mfu_analytic"] is None


@pytest.mark.slow
def test_lob_bench_full_depth_sweep_at_1024_books():
    """The acceptance-criteria shape: a >=1024-book vmapped sweep still
    emits a schema-valid record (slow: ~1 min of CPU matching)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--lob",
         "--books", "1024", "--messages", "64", "--iters", "2",
         "--depths", "8,24"],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=480,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    payload = json.loads(
        [ln for ln in proc.stdout.strip().splitlines() if ln.strip()][-1]
    )
    problems = validate_record(payload)
    assert not problems, (problems, payload)
    assert payload["books"] == 1024
    assert payload["messages_per_stream"] == 64
    assert payload["value"] > 0
    assert set(payload["depth_sweep"]) == {"8", "24"}


@pytest.mark.parametrize("script", [
    "bench.py", "bench_infer.py", "tools/multichip_bench.py",
    "tools/serve_load.py",
])
def test_bench_exits_nonzero_when_the_device_probe_fails(script):
    """No device, no row: a benchmark whose first device op fails exits
    non-zero with JAX's error.  It never prints a zero-valued record
    and exits 0 (what the old probe watchdog did)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "no_such_platform"  # backend init must fail
    proc = subprocess.run(
        [sys.executable, str(REPO / script), "--quick"],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert "no_such_platform" in proc.stderr
    assert '"metric"' not in proc.stdout, proc.stdout[-2000:]


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """CPU rehearsal of chip_smoke.py: it must stop at its `device`
    phase, print "ok": false and exit non-zero — there is no CPU mode."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0, proc.stdout[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    first, last = json.loads(lines[0]), json.loads(lines[-1])
    assert first["phase"] == "device" and first["ok"] is False
    assert len(lines) == 2, lines  # no work phase ran
    assert last["ok"] is False and set(last) == {"ok", "device"}
    assert last["device"]["platform"] == "cpu"
