"""Tier-1 smoke for the serving benchmark contract:
``python bench_infer.py --quick`` must exit 0 on CPU and end its
stdout with the single JSON line (decisions_per_sec_per_chip / p50_ms /
p99_ms) that downstream dashboards parse unconditionally
(docs/serving.md, Benchmark contract)."""
import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "tools"))

from check_bench_contract import validate_record  # noqa: E402


def test_bench_infer_quick_prints_single_json_line_contract():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # share the suite's persistent compile cache so the smoke pays the
    # bucket ladder's compiles at most once across CI runs
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench_infer.py"), "--quick"],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    assert lines, f"bench printed nothing to stdout: {proc.stderr[-2000:]}"
    payload = json.loads(lines[-1])  # the contract: final line IS the JSON
    # committed key-set contract (tools/bench_contract_schema.json) —
    # includes the r7 "telemetry" scrape cross-check sub-dict
    problems = validate_record(payload)
    assert not problems, (problems, payload)
    for key in ("metric", "value", "decisions_per_sec_per_chip",
                "p50_ms", "p99_ms", "speedup_vs_sequential"):
        assert key in payload, (key, payload)
    assert payload["metric"] == "serve_decisions_per_sec_per_chip"
    assert payload["decisions_per_sec_per_chip"] > 0
    assert payload["p99_ms"] >= payload["p50_ms"] > 0
    # the whole point of the engine: the warm boot absorbed every
    # compile, the serving path never traced
    assert payload["late_compiles"] == 0
    # serving SLO contract (docs/serving.md, Overload behavior): the
    # line always carries the overload trio, and the scripted seeded
    # burst-overload scenario must measurably engage the admission
    # control — a scenario that sheds nothing measures nothing
    for key in ("shed_rate", "deadline_miss_rate", "overload"):
        assert key in payload, (key, payload)
    over = payload["overload"]
    assert over["submitted"] == (
        over["served"] + over["shed"] + over["deadline_missed"]
        + over["failed"]
    )
    assert payload["shed_rate"] > 0, over
    assert payload["deadline_miss_rate"] > 0, over
    assert over["served"] > 0, over
    assert over["p99_ms"] > 0, over
