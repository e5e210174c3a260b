#!/usr/bin/env bash
# Canonical tier-1 test invocation (the known-good procedure, in the
# repo instead of session notes — VERDICT.md round-5 item 8).
#
#   tools/run_tests.sh            # tier-1 (everything not marked slow)
#   tools/run_tests.sh -k serve   # extra args forwarded to pytest
#
# Compile cache: tests/conftest.py follows the one rule of
# gymfx_tpu/compile_cache.py — JAX_COMPILATION_CACHE_DIR if it is set,
# else the fixed, git-ignored .jax_cache/ in the checkout — and exports
# the directory, so the main process warms it for the subprocess tests
# (CLI roundtrips, bench smokes).  The per-session mkdtemp directory of
# earlier rounds guarded against a stale-cache crash that PR 22 could
# not reproduce on JAX 0.9.0 (a second run of the heavy trainer files
# over the first run's cache: 62 passed, 183 s against 321 s).  Every
# leg below is a CPU leg: it sets JAX_PLATFORMS=cpu or never imports JAX.
#
# After the suite: the scenario robustness gate in quick mode (three
# scengen presets + the serving-fallback leg, schema-pinned report —
# docs/scenarios.md), the bench-regression sentinel over the committed
# BENCH_r*/MULTICHIP_r* rows (plus a synthetic-regression fixture that
# must fail), a run-ledger smoke (tiny training run, ledger validated
# against the committed schema), a performance-observatory smoke (a
# profiler-armed training run must land a capture bundle whose report
# validates against profile_report_schema.json, reconciles trace
# attribution with the measured phase split, and whose --compare gate
# fails a synthetic kernel regression), a soak-quick leg (two
# retrain->gate->swap->serve cycles under the fault grammar: schema-
# valid soak report, zero dropped decisions, zero late compiles,
# bitwise-verified rollback — docs/resilience.md), a fleet-chaos quick
# leg (three-replica decision fleet loses a replica to a scripted kill
# mid-burst: schema-valid fleet report, zero dropped requests, digest-
# verified failover, carry sessions bitwise-identical to the unfailed
# baseline — docs/serving.md "Decision fleet"), then a telemetry
# smoke
# (ephemeral /metrics endpoint, one scrape, assert non-empty —
# docs/observability.md) and a per-run summary row appended to
# PROGRESS.jsonl through the JSONL sink.
set -uo pipefail
cd "$(dirname "$0")/.."

start=$(date +%s)
rc=0
env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider "$@" || rc=$?
wall=$(( $(date +%s) - start ))

# scenario robustness gate, quick matrix (report to stdout; non-zero on
# any failed preset or serving leg)
gate_rc=0
env JAX_PLATFORMS=cpu python tools/scenario_gate.py --quick \
    > /dev/null || gate_rc=$?
echo "scenario gate (quick): rc=$gate_rc"

# r10 MFU push + billion-bar data path: bench contract smoke with the
# fused env-dynamics kernels AND the compressed stream probe in pallas
# interpret mode — exercises both kernel paths on CPU CI and pins the
# row (incl. overlap_ms_saved / update_gemm_frac / mfu_analytic /
# stream_bars_per_sec / data_compression_ratio / resident_bars)
# against tools/bench_contract_schema.json; the codec must hold
# ratio >= 3 and a real resident-bars win even at the --quick tape size
bench_row=$(mktemp)
bench_rc=0
env JAX_PLATFORMS=cpu python bench.py --quick \
        --rollout_env_kernel interpret --data_compress interpret \
    | tee "$bench_row" \
    | env JAX_PLATFORMS=cpu python tools/check_bench_contract.py \
    || bench_rc=$?
if [ "$bench_rc" -eq 0 ]; then
    python - "$bench_row" <<'EOF' || bench_rc=$?
import json
import sys

row = json.loads(
    [ln for ln in open(sys.argv[1], encoding="utf-8") if ln.strip()][-1]
)
assert row["stream_bars_per_sec"] > 0, row
assert row["data_compression_ratio"] >= 3.0, row["data_compression_ratio"]
assert row["resident_bars"] > 2 * row["resident_bars_uncompressed"], row
print(f"stream probe OK (ratio {row['data_compression_ratio']}, "
      f"{row['resident_bars']} resident bars vs "
      f"{row['resident_bars_uncompressed']} uncompressed at "
      f"{row['stream_hbm_budget_mb']} MiB)")
EOF
fi
rm -f "$bench_row"
echo "bench contract (quick, env kernel + stream probe): rc=$bench_rc"

# billion-bar data path: a 2-superstep compressed training run
# (interpret decode kernel) must be BITWISE identical to the
# uncompressed path — (a) curriculum training over a compressed tape
# library vs the same library uncompressed, (b) a compressed streamed
# rollout vs the fully-resident tape
stream_rc=0
env JAX_PLATFORMS=cpu python - <<'EOF' || stream_rc=$?
import numpy as np

import jax

from gymfx_tpu.config.defaults import DEFAULT_VALUES
from gymfx_tpu.core.rollout import DRIVERS
from gymfx_tpu.core.runtime import Environment
from gymfx_tpu.data.feed import market_data_nbytes
from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from

BASE = dict(DEFAULT_VALUES)
BASE.update({
    "window_size": 8, "num_envs": 4, "ppo_horizon": 8,
    "ppo_epochs": 1, "ppo_minibatches": 2,
    "policy_kwargs": {"hidden": [16, 16]}, "seed": 1,
    "feed": "curriculum",
    "tapes": "scengen:flash_crash@2,scengen:range_chop@1",
    "scengen_bars": 512, "scengen_seed": 3,
    "scengen_snap_to_tick": True,
})


def train(compress):
    env = Environment(dict(BASE, data_compress=compress))
    tr = PPOTrainer(env, ppo_config_from(env.config))
    state = tr.init_state(0)
    for it in range(2):  # 2 supersteps, tape swap at each boundary
        _i, _label, tape = tr.curriculum.pick(it)
        state, _ = tr._train_step_data(state, tape)
    return [np.asarray(x) for x in jax.tree.leaves(state.params)]


ref, got = train("off"), train("interpret")
assert all(a.tobytes() == b.tobytes() for a, b in zip(ref, got)), \
    "compressed curriculum training diverged from the uncompressed path"
print("compressed curriculum training bitwise OK (2 supersteps)")

scfg = dict(DEFAULT_VALUES)
scfg.update({
    "feed": "scengen", "scengen_preset": "regime_mix",
    "scengen_bars": 2048, "scengen_seed": 0,
    "scengen_snap_to_tick": True, "window_size": 16,
})
resident = Environment(dict(scfg))
total = market_data_nbytes(resident.data)
streamed = Environment(dict(
    scfg, stream_hbm_budget_mb=total / 4 / 2**20,
    data_compress="interpret",
))
assert streamed.streaming and streamed.streamer.num_shards >= 3
driver = DRIVERS["buy_hold"]()
s_ref, out_ref = resident.rollout(driver, 2047, seed=0)
s_str, out_str = streamed.rollout(driver, 2047, seed=0)
for key in out_ref:
    a, b = np.asarray(out_ref[key]), np.asarray(out_str[key])
    assert a.tobytes() == b.tobytes(), f"outputs[{key}]"
for a, b in zip(jax.tree.leaves(s_ref), jax.tree.leaves(s_str)):
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), "state"
print(f"compressed streamed rollout bitwise OK "
      f"({streamed.streamer.num_shards} shards, ratio "
      f"{streamed.streamer.compression_ratio:.2f})")
EOF
echo "compressed data path (training + stream parity): rc=$stream_rc"

# bench-regression sentinel: the committed BENCH_r*/MULTICHIP_r* rows
# must keep a healthy trajectory (explicitly non-comparable rows are
# skipped BY KEY), and the gate must still FAIL when handed a synthetic
# 25% regression — a sentinel that cannot fail is not a gate
sentinel_rc=0
python tools/bench_sentinel.py --check || sentinel_rc=$?
echo "bench sentinel (committed rows): rc=$sentinel_rc"
if [ "$sentinel_rc" -eq 0 ]; then
    python - <<'EOF' || sentinel_rc=$?
import json
import subprocess
import sys
import tempfile
from pathlib import Path

with tempfile.TemporaryDirectory() as d:
    for n, value in ((1, 100.0), (2, 75.0)):  # 25% drop: must fail
        (Path(d) / f"BENCH_r{n:02d}.json").write_text(json.dumps({
            "n": n, "rc": 0, "cmd": "synthetic-regression-fixture",
            "parsed": {"metric": "ppo_env_steps_per_sec_per_chip",
                       "value": value, "unit": "env steps/sec"},
        }))
    rc = subprocess.run(
        [sys.executable, "tools/bench_sentinel.py", "--check", "--dir", d],
        capture_output=True,
    ).returncode
if rc != 1:
    print(f"bench sentinel did NOT flag a synthetic regression (rc={rc})")
    sys.exit(1)
print("bench sentinel correctly fails the synthetic-regression fixture")
EOF
fi

# run-ledger smoke: a two-iteration CPU training run with the ledger
# (+ flight recorder + compile watch) on must produce a ledger that
# validates against the committed schema end-to-end
ledger_rc=0
env JAX_PLATFORMS=cpu python - <<'EOF' || ledger_rc=$?
import sys
import tempfile
from pathlib import Path

from gymfx_tpu.config.defaults import DEFAULT_VALUES
from gymfx_tpu.telemetry.ledger import read_ledger, validate_ledger
from gymfx_tpu.train.ppo import train_from_config

with tempfile.TemporaryDirectory() as d:
    ledger = str(Path(d) / "ledger.jsonl")
    cfg = dict(DEFAULT_VALUES)
    cfg.update({
        "input_file": "tests/data/eurusd_uptrend.csv",
        "window_size": 8, "num_envs": 4, "ppo_horizon": 16,
        "ppo_epochs": 1, "ppo_minibatches": 1,
        "policy_kwargs": {"hidden": [16, 16]},
        "train_total_steps": 128, "seed": 1,
        "telemetry_ledger": ledger,
        "telemetry_compile_watch": True,
    })
    train_from_config(cfg)
    problems = validate_ledger(ledger)
    if problems:
        print("LEDGER SCHEMA VIOLATIONS:", *problems, sep="\n  ")
        sys.exit(1)
    kinds = [r["kind"] for r in read_ledger(ledger)]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end", kinds
    assert "superstep_dispatch" in kinds and "compile_end" in kinds, kinds
    print(f"run-ledger smoke OK ({len(kinds)} rows, schema-valid)")
EOF
echo "run-ledger smoke: rc=$ledger_rc"

# performance-observatory smoke: a two-superstep CPU training run with
# the profiler armed must land a manifested capture bundle; the report
# CLI must render it schema-valid with the trace-measured rollout
# fraction reconciling against measure_phase_split and mfu_measured
# populated; and the per-kernel --compare gate must FAIL a synthetic
# kernel regression — a compare that cannot fail is not a gate
profile_rc=0
env JAX_PLATFORMS=cpu python - <<'EOF' || profile_rc=$?
import json
import subprocess
import sys
import tempfile
from pathlib import Path

from gymfx_tpu.config.defaults import DEFAULT_VALUES
from gymfx_tpu.telemetry.attribution import validate_profile_report
from gymfx_tpu.telemetry.profiler import find_captures
from gymfx_tpu.train.ppo import train_from_config

with tempfile.TemporaryDirectory() as d:
    prof = str(Path(d) / "prof")
    cfg = dict(DEFAULT_VALUES)
    cfg.update({
        # the CI reconciliation shape: large enough that device work
        # dominates thunk overhead, small enough for sub-minute CI
        "window_size": 32, "num_envs": 64, "ppo_horizon": 32,
        "ppo_epochs": 2, "ppo_minibatches": 2,
        "policy_kwargs": {"hidden": [64, 64]},
        "train_total_steps": 64 * 32 * 2, "seed": 1,
        "telemetry_profile_dir": prof,
    })
    train_from_config(cfg)
    caps = find_captures(prof)
    if not caps:
        print("observatory smoke: no capture bundle written")
        sys.exit(1)
    out = subprocess.run(
        [sys.executable, "tools/profile_report.py", caps[-1]],
        capture_output=True, text=True,
    )
    if out.returncode != 0:
        print("profile_report.py failed:", out.stdout, out.stderr)
        sys.exit(1)
    report_path = Path(caps[-1]) / "profile_report.json"
    report = json.loads(report_path.read_text(encoding="utf-8"))
    problems = validate_profile_report(report)
    if problems:
        print("PROFILE REPORT SCHEMA VIOLATIONS:", *problems, sep="\n  ")
        sys.exit(1)
    rec = report["reconciliation"]
    meas = report["mfu_measured"]
    assert rec["within_tolerance"], rec
    assert meas["device_ms_per_step"] > 0, meas
    assert meas["flops_per_step"] > 0 and meas["achieved_flops_per_sec"], meas
    print(f"observatory smoke OK (trace rollout frac "
          f"{rec['trace_rollout_frac']:.3f} vs split "
          f"{rec['split_rollout_frac']:.3f}, "
          f"{meas['achieved_flops_per_sec']:.3g} FLOP/s measured)")

    # synthetic kernel regression: double the top kernel's per-step
    # time in a copy of the real report — --compare must exit 1
    worse = json.loads(report_path.read_text(encoding="utf-8"))
    kernels = worse["trace"]["top_kernels"]
    assert kernels, "report has no kernels to regress"
    kernels[0]["total_ms_per_step"] *= 2.0
    kernels[0]["total_ms"] *= 2.0
    new_path = Path(d) / "regressed_report.json"
    new_path.write_text(json.dumps(worse), encoding="utf-8")
    rc = subprocess.run(
        [sys.executable, "tools/profile_report.py", str(new_path),
         "--compare", str(report_path), "--min-ms", "0"],
        capture_output=True,
    ).returncode
    if rc != 1:
        print(f"profile --compare did NOT flag a doubled kernel (rc={rc})")
        sys.exit(1)
    print("profile --compare correctly fails the synthetic kernel "
          "regression")
EOF
echo "performance observatory smoke: rc=$profile_rc"

# soak-quick leg: a two-cycle retrain->gate->swap->serve loop on CPU
# under the default fault grammar must emit a schema-valid soak report
# with zero dropped decisions, zero late compiles, and a bitwise-
# verified rollback (docs/resilience.md, "Continuous-learning loop")
soak_rc=0
env JAX_PLATFORMS=cpu python - <<'EOF' || soak_rc=$?
import json
import subprocess
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, "tools")
from soak import validate_soak_report  # noqa: E402

with tempfile.TemporaryDirectory() as d:
    out = Path(d) / "soak_report.json"
    run = subprocess.run(
        [sys.executable, "tools/soak.py", "--quick", "--cycles", "2",
         "--envs", "64", "--workdir", d, "--out", str(out)],
        capture_output=True, text=True,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    if run.returncode != 0 or not out.exists():
        print("soak CLI failed:", run.stdout[-2000:], run.stderr[-2000:])
        sys.exit(run.returncode or 1)
    report = json.loads(out.read_text(encoding="utf-8"))
    problems = validate_soak_report(report)
    if problems:
        print("SOAK REPORT SCHEMA VIOLATIONS:", *problems, sep="\n  ")
        sys.exit(1)
    assert report["passed"] is True, report
    assert report["dropped_decisions"] == 0, report
    assert report["late_compiles"] == 0, report
    assert report["rollback_verified"] is True, report
    print(f"soak-quick OK ({report['completed_cycles']} cycles, "
          f"{report['submitted_decisions']} decisions, "
          f"{report['fault_errors']} typed fault errors, "
          f"swap p99 {report['swap_latency_p99_ms']:.2f} ms)")
EOF
echo "soak-quick (2 cycles, fault grammar): rc=$soak_rc"

# fleet-chaos quick leg: a three-replica decision fleet loses replica 1
# to a scripted kill mid-burst and must emit a schema-valid fleet
# report with zero dropped requests, a digest-verified failover, and
# every session's decision stream bitwise identical to the unfailed
# baseline (docs/serving.md, "Decision fleet")
fleet_rc=0
env JAX_PLATFORMS=cpu python - <<'EOF' || fleet_rc=$?
import json
import subprocess
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, "tools")
from fleet_chaos import validate_fleet_report  # noqa: E402

with tempfile.TemporaryDirectory() as d:
    out = Path(d) / "fleet_report.json"
    run = subprocess.run(
        [sys.executable, "tools/fleet_chaos.py", "--quick",
         "--workdir", d, "--out", str(out)],
        capture_output=True, text=True,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    if run.returncode != 0 or not out.exists():
        print("fleet chaos CLI failed:",
              run.stdout[-2000:], run.stderr[-2000:])
        sys.exit(run.returncode or 1)
    report = json.loads(out.read_text(encoding="utf-8"))
    problems = validate_fleet_report(report)
    if problems:
        print("FLEET REPORT SCHEMA VIOLATIONS:", *problems, sep="\n  ")
        sys.exit(1)
    assert report["passed"] is True, report
    assert report["dropped"] == 0, report
    assert report["failovers"] >= 1, report
    assert report["failover_verified"] is True, report
    assert report["carry_parity"] is True, report
    print(f"fleet-chaos quick OK ({report['decided']} decisions, "
          f"{report['failovers']} failovers, "
          f"{report['parity_sessions']}/{report['sessions']} sessions "
          f"bitwise-identical)")
EOF
echo "fleet-chaos quick (3 replicas, scripted kill): rc=$fleet_rc"

# elastic-chaos quick leg: training on a 4-device virtual mesh loses
# device 3 to a scripted mesh= kill, must re-plan to the survivor
# shape, resume from the last digest-verified checkpoint with zero
# supersteps lost past it, ledger the degrade/resume pair in
# schema-valid per-attempt ledgers, and replay bitwise identical
# (docs/resilience.md, "Elastic training")
elastic_rc=0
env JAX_PLATFORMS=cpu python - <<'EOF' || elastic_rc=$?
import json
import subprocess
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, "tools")
from elastic_chaos import validate_elastic_report  # noqa: E402

with tempfile.TemporaryDirectory() as d:
    out = Path(d) / "elastic_report.json"
    run = subprocess.run(
        [sys.executable, "tools/elastic_chaos.py", "--quick",
         "--workdir", d, "--out", str(out)],
        capture_output=True, text=True,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    if run.returncode != 0 or not out.exists():
        print("elastic chaos CLI failed:",
              run.stdout[-2000:], run.stderr[-2000:])
        sys.exit(run.returncode or 1)
    report = json.loads(out.read_text(encoding="utf-8"))
    problems = validate_elastic_report(report)
    if problems:
        print("ELASTIC REPORT SCHEMA VIOLATIONS:", *problems, sep="\n  ")
        sys.exit(1)
    assert report["passed"] is True, report
    assert report["degrades"] >= 1, report
    assert report["resumes"] >= 1, report
    assert report["lost_supersteps_past_checkpoint"] == 0, report
    assert report["ledger_valid"] is True, report
    assert report["replay_parity"] is True, report
    print(f"elastic-chaos quick OK (mesh {report['mesh_before']} -> "
          f"{report['mesh_after']}, resume at step "
          f"{report['resume_step']}, replay bitwise-identical)")
EOF
echo "elastic-chaos quick (4-device mesh, scripted device loss): rc=$elastic_rc"

# serve-load quick leg: the open-loop sustained-load harness over the
# device-resident slot path (docs/serving.md, "Device-resident
# sessions") must emit a schema-valid serve_load row with zero dropped
# requests and a bitwise slot-vs-host-carry parity verdict
serveload_rc=0
env JAX_PLATFORMS=cpu python - <<'EOF' || serveload_rc=$?
import json
import subprocess
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, "tools")
from check_bench_contract import validate_record  # noqa: E402

with tempfile.TemporaryDirectory() as d:
    out = Path(d) / "serve_load_report.json"
    run = subprocess.run(
        [sys.executable, "tools/serve_load.py", "--quick",
         "--policy", "lstm", "--session_slots", "8",
         "--batch_mode", "exact", "--report", str(out)],
        capture_output=True, text=True,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    if run.returncode != 0 or not out.exists():
        print("serve_load CLI failed:", run.stdout[-2000:],
              run.stderr[-2000:])
        sys.exit(run.returncode or 1)
    line = [ln for ln in run.stdout.splitlines() if ln.strip()][-1]
    row = json.loads(line)
    problems = validate_record(row)
    if problems:
        print("SERVE LOAD ROW SCHEMA VIOLATIONS:", *problems, sep="\n  ")
        sys.exit(1)
    report = json.loads(out.read_text(encoding="utf-8"))
    assert validate_record(report) == [], "report diverged from row schema"
    assert row["dropped"] == 0, row
    assert row["slot_parity"] is True, row
    assert row["served"] > 0, row
    assert report["late_compiles"] == 0, report
    assert report["pipeline"] is True, report
    print(f"serve-load quick OK ({row['served']}/{row['offered']} served "
          f"at {row['sustained_decisions_per_sec']}/s sustained, "
          f"p99 {row['p99_ms']} ms, slot parity bitwise)")
EOF
echo "serve-load quick (open loop, slot path): rc=$serveload_rc"

# telemetry smoke + PROGRESS row (registry/http/sink are jax-free:
# this is sub-second and runs even when the suite failed, so the row
# records the failure too)
smoke_rc=0
python - "$rc" "$wall" <<'EOF' || smoke_rc=$?
import subprocess
import sys

from gymfx_tpu.telemetry import MetricsRegistry
from gymfx_tpu.telemetry.http import TelemetryServer, scrape
from gymfx_tpu.telemetry.sink import append_jsonl

rc, wall = int(sys.argv[1]), float(sys.argv[2])
reg = MetricsRegistry()
reg.counter("gymfx_smoke_runs_total", "run_tests.sh telemetry smoke").inc()
with TelemetryServer(reg, port=0) as srv:
    url = srv.url
    text = scrape(url + "/metrics")
assert text.strip(), "telemetry smoke: empty /metrics exposition"
assert "gymfx_smoke_runs_total 1" in text, text
print(f"telemetry smoke OK ({len(text)} bytes from {url}/metrics)")

def _git_int(*args):
    try:
        out = subprocess.run(
            ("git",) + args, capture_output=True, text=True, timeout=10
        ).stdout.split()
        return int(out[0]) if out else None
    except Exception:
        return None

append_jsonl("PROGRESS.jsonl", {
    "kind": "test_run",
    "wall_s": float(wall),
    "rc": rc,
    "commits": _git_int("rev-list", "--count", "HEAD"),
})
EOF

if [ "$rc" -ne 0 ]; then
    exit "$rc"
fi
if [ "$gate_rc" -ne 0 ]; then
    exit "$gate_rc"
fi
if [ "$bench_rc" -ne 0 ]; then
    exit "$bench_rc"
fi
if [ "$stream_rc" -ne 0 ]; then
    exit "$stream_rc"
fi
if [ "$sentinel_rc" -ne 0 ]; then
    exit "$sentinel_rc"
fi
if [ "$ledger_rc" -ne 0 ]; then
    exit "$ledger_rc"
fi
if [ "$profile_rc" -ne 0 ]; then
    exit "$profile_rc"
fi
if [ "$soak_rc" -ne 0 ]; then
    exit "$soak_rc"
fi
if [ "$fleet_rc" -ne 0 ]; then
    exit "$fleet_rc"
fi
if [ "$elastic_rc" -ne 0 ]; then
    exit "$elastic_rc"
fi
if [ "$serveload_rc" -ne 0 ]; then
    exit "$serveload_rc"
fi
exit "$smoke_rc"
