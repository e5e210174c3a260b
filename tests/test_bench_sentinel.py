"""Tier-1 coverage for the bench-regression sentinel (tools/
bench_sentinel.py): the committed rows must pass the gate with every
skip attributed BY KEY (not by filename folklore), a synthetic
regression and a schema-drifted current-generation row must fail it,
and the shared ``emit_bench_record`` path must stamp the comparability
keys the sentinel filters on.
"""
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "tools"))

from bench_sentinel import (  # noqa: E402
    classify,
    load_bench_rows,
    main as sentinel_main,
    sentinel_report,
)

METRIC = "ppo_env_steps_per_sec_per_chip"


def _wrapper(n, value, *, metric=METRIC, rc=0, **extra):
    parsed = {"metric": metric, "value": value, "unit": "env steps/sec"}
    parsed.update(extra)
    return {"n": n, "rc": rc, "cmd": "synthetic", "parsed": parsed}


def _write_rows(tmp_path, wrappers):
    tmp_path.mkdir(parents=True, exist_ok=True)
    for i, w in enumerate(wrappers, start=1):
        (tmp_path / f"BENCH_r{i:02d}.json").write_text(
            json.dumps(w), encoding="utf-8"
        )
    return str(tmp_path)


# ----------------------------------------------------------------------
# classify: the comparability verdict, key-driven not filename-driven


def test_classify_explicit_key_wins_over_everything():
    # a declared-comparable row is an anchor even on a cpu platform
    v = classify(_wrapper(1, 5.0, comparable=True, platform="cpu"))
    assert v["comparable"] is True and v["why"] == "declared"
    # a declared-non-comparable row is skipped even with a healthy value
    v = classify(_wrapper(1, 5.0, comparable=False))
    assert v["comparable"] is False
    assert v["why"] == "declared_non_comparable"


def test_classify_legacy_heuristic():
    assert classify({"parsed": None})["why"] == "no_record"
    assert classify(_wrapper(1, 5.0, rc=3))["why"] == "rc=3"
    v = classify(_wrapper(1, 0.0, unit="x (BENCH ABORTED: probe timeout)"))
    assert not v["comparable"] and v["why"] == "aborted"
    assert classify(_wrapper(1, 0.0))["why"] == "non_positive_value"
    assert classify(_wrapper(1, 5.0, platform="cpu"))["why"] == "cpu_proxy"
    v = classify(_wrapper(1, 5.0))
    assert v["comparable"] is True and v["why"] == "legacy_heuristic"


# ----------------------------------------------------------------------
# the committed rows: the gate the repo actually ships under


def _cpu_proxy_row(n, value=28478.0):
    """A current-generation row (carries every contract key of its
    metric) that was measured on a CPU and says so — the shape of the
    proxy rows PRs 10-16 committed."""
    return _wrapper(
        n, value, vs_baseline=0.228, mfu=None, supersteps=2,
        dispatch_overhead_frac=0.0239, per_step_ms_single_dispatch=294.704,
        rollout_ms=88.737, update_ms=198.195, overlap_ms_saved=0.23,
        update_gemm_frac=0.92, rollout_env_kernel="on",
        analytic_flops_per_step=9797894144.0, hw_flops_peak=None,
        mfu_analytic=None, device_memory_bytes=None, comparable=False,
        platform="cpu", device_kind="cpu",
    )


def _aborted_row(n):
    """A row whose benchmark printed a zero value and a BENCH ABORTED
    unit instead of failing — what the old device-probe watchdog wrote
    (bench.py now exits non-zero instead; the sentinel still has to
    classify such a row in an old directory)."""
    return _wrapper(
        n, 0.0, vs_baseline=0.0,
        unit="env steps/sec/chip (BENCH ABORTED: device probe timed out "
             "— accelerator unreachable)",
    )


def test_committed_rows_pass_the_gate_with_attributed_skips(tmp_path):
    # the rows the repo still ships (CPU proxy rows r07/r08 and the
    # MULTICHIP dry runs; the chip-era rows were deleted in PR 22)
    rows = load_bench_rows(str(REPO))
    assert rows, "committed BENCH_r*/MULTICHIP_r* rows must exist"
    report = sentinel_report(rows)
    assert report["schema_drift"] == []
    assert report["regressions"] == []
    assert report["ok"] is True
    skips = {s["file"]: s["why"] for s in report["skipped"]}
    for proxy in ("BENCH_r07.json", "BENCH_r08.json"):
        assert skips.get(proxy) == "declared_non_comparable"

    # an aborted row and a row that declares itself non-comparable,
    # between two real-device rows: both skipped, each for ITS reason
    d = _write_rows(tmp_path, [
        _aborted_row(1),
        _wrapper(2, 7_950_597.7),
        _wrapper(3, 8_339_102.8),
        _cpu_proxy_row(4),
    ])
    rows = load_bench_rows(d)
    report = sentinel_report(rows)
    assert report["schema_drift"] == []
    assert report["regressions"] == []
    assert report["ok"] is True
    skips = {s["file"]: s["why"] for s in report["skipped"]}
    # the aborted row: heuristically skipped
    assert skips.get("BENCH_r01.json") == "aborted"
    # the CPU proxy SAYS so via the comparable key — the explicit
    # declaration, not the filename, is why it is skipped
    assert skips.get("BENCH_r04.json") == "declared_non_comparable"
    r04 = next(r for r in rows if r["file"] == "BENCH_r04.json")
    assert r04["record"]["comparable"] is False
    assert r04["record"]["platform"] == "cpu"
    # the trajectory still anchors on the real-device rows
    points = report["metrics"][METRIC]["points"]
    assert [p["file"] for p in points] == ["BENCH_r02.json", "BENCH_r03.json"]


def test_sentinel_cli_passes_on_committed_rows(capsys):
    assert sentinel_main(["--check", "--dir", str(REPO)]) == 0
    out = capsys.readouterr().out
    assert "bench sentinel OK" in out


# ----------------------------------------------------------------------
# regression detection


def test_synthetic_regression_fails_the_gate(tmp_path):
    d = _write_rows(tmp_path, [
        _wrapper(1, 100.0),
        _wrapper(2, 79.9),  # 20.1% below best previous at threshold 20%
    ])
    report = sentinel_report(load_bench_rows(d))
    assert report["ok"] is False
    assert len(report["regressions"]) == 1
    assert METRIC in report["regressions"][0]
    assert sentinel_main(["--check", "--dir", d]) == 1


def test_regression_threshold_boundary_passes(tmp_path):
    d = _write_rows(tmp_path, [
        _wrapper(1, 100.0),
        _wrapper(2, 80.0),  # exactly at the threshold: not a regression
    ])
    report = sentinel_report(load_bench_rows(d))
    assert report["ok"] is True and report["regressions"] == []
    assert report["metrics"][METRIC]["vs_best_previous"] == 0.8


def test_regression_measured_against_best_previous_not_last(tmp_path):
    # a dip followed by partial recovery still regresses vs the PEAK
    d = _write_rows(tmp_path, [
        _wrapper(1, 100.0), _wrapper(2, 50.0), _wrapper(3, 70.0),
    ])
    report = sentinel_report(load_bench_rows(d))
    assert report["ok"] is False
    assert report["metrics"][METRIC]["best_previous"] == 100.0


def test_non_comparable_rows_never_anchor_the_trajectory(tmp_path):
    # the latest row is a declared CPU proxy: skipped, not compared
    d = _write_rows(tmp_path, [
        _wrapper(1, 100.0),
        _wrapper(2, 1.0, comparable=False, platform="cpu",
                 device_kind="cpu"),
    ])
    report = sentinel_report(load_bench_rows(d))
    # only schema drift can fail here (the r02 row is synthetic and
    # does not carry the full contract keys) — so validate shape-only
    assert report["regressions"] == []
    points = report["metrics"][METRIC]["points"]
    assert [p["value"] for p in points] == [100.0]


# ----------------------------------------------------------------------
# schema drift: current-generation rows must match the contract


def test_schema_drift_fails_only_rows_carrying_the_comparable_key(tmp_path):
    # legacy row missing contract keys: grandfathered, trajectory-only
    legacy = _wrapper(1, 100.0)
    # current-generation row (has `comparable`) missing required keys
    drifted = _wrapper(2, 110.0, comparable=True, platform="tpu")
    d = _write_rows(tmp_path, [legacy, drifted])
    report = sentinel_report(load_bench_rows(d))
    assert report["ok"] is False
    assert report["regressions"] == []
    assert report["schema_drift"]
    assert all("BENCH_r02.json" in p for p in report["schema_drift"])
    assert sentinel_main(["--check", "--dir", d]) == 1


def test_proxy_row_would_fail_if_a_contract_key_were_dropped(tmp_path):
    whole = _cpu_proxy_row(6)
    d = _write_rows(tmp_path / "whole", [whole])
    assert sentinel_report(load_bench_rows(d))["schema_drift"] == []
    src = json.loads(json.dumps(whole))
    assert "comparable" in src["parsed"]
    del src["parsed"]["platform"]  # drift a required key off the row
    d = _write_rows(tmp_path / "drifted", [src])
    report = sentinel_report(load_bench_rows(d))
    assert report["ok"] is False
    assert any("platform" in p for p in report["schema_drift"])


def test_unparseable_wrapper_is_skipped_not_fatal(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text("{nope", encoding="utf-8")
    (tmp_path / "BENCH_r02.json").write_text(
        json.dumps(_wrapper(2, 100.0)), encoding="utf-8")
    rows = load_bench_rows(str(tmp_path))
    report = sentinel_report(rows)
    assert report["ok"] is True
    assert any(s["why"].startswith("unparseable") for s in report["skipped"])


def test_sentinel_cli_fails_on_empty_dir(tmp_path):
    assert sentinel_main(["--check", "--dir", str(tmp_path)]) == 1


# ----------------------------------------------------------------------
# emit_bench_record: the stamp the sentinel keys on


def test_emit_bench_record_stamps_comparability_on_cpu(capsys):
    from gymfx_tpu.bench_util import emit_bench_record

    record = emit_bench_record({"metric": METRIC, "value": 123.0})
    out_line = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(out_line) == record
    assert record["platform"] == "cpu"
    assert record["device_kind"]
    assert record["comparable"] is False  # CPU proxies never anchor
    assert classify({"rc": 0, "parsed": record})["why"] == (
        "declared_non_comparable"
    )


def test_emit_bench_record_caller_verdict_wins(capsys):
    from gymfx_tpu.bench_util import emit_bench_record

    record = emit_bench_record(
        {"metric": METRIC, "value": 123.0, "comparable": True})
    capsys.readouterr()
    assert record["comparable"] is True  # explicit verdict not clobbered


def test_emit_bench_record_publishes_to_active_ledger(tmp_path, capsys):
    from gymfx_tpu.bench_util import emit_bench_record
    from gymfx_tpu.telemetry.ledger import (
        RunLedger,
        read_ledger,
        set_active_ledger,
        validate_ledger,
    )

    led = RunLedger(str(tmp_path / "ledger.jsonl"))
    try:
        set_active_ledger(led)
        emit_bench_record({"metric": METRIC, "value": 123.0})
    finally:
        set_active_ledger(None)
    capsys.readouterr()
    led.close()
    assert validate_ledger(led.path) == []
    row = next(r for r in read_ledger(led.path) if r["kind"] == "bench_row")
    assert row["metric"] == METRIC and row["value"] == 123.0
    assert row["comparable"] is False and row["platform"] == "cpu"


def test_sentinel_publishes_gate_verdict_to_active_ledger(tmp_path):
    from gymfx_tpu.telemetry.ledger import (
        RunLedger,
        read_ledger,
        set_active_ledger,
        validate_ledger,
    )

    d = _write_rows(tmp_path / "rows", [_wrapper(1, 100.0)])
    led = RunLedger(str(tmp_path / "ledger.jsonl"))
    try:
        set_active_ledger(led)
        assert sentinel_main(["--check", "--dir", d, "--json"]) == 0
    finally:
        set_active_ledger(None)
    led.close()
    assert validate_ledger(led.path) == []
    row = next(r for r in read_ledger(led.path)
               if r["kind"] == "gate_verdict")
    assert row["verdict"] == "pass" and row["gate"] == "bench_sentinel"
