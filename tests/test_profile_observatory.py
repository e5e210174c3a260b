"""Performance observatory: trace parsing, capture cadence, attribution.

Covers the trace-driven measurement loop (docs/observability.md
"Performance observatory"):

  * the stdlib perfetto parser against the committed golden trace
    (tests/data/golden_profile.trace.json.gz — hand-built in the
    jax.profiler CPU layout): device/host lane splitting, per-op
    SELF-time aggregation (the `while` container keeps only its loop
    overhead), interval-union busy time vs window, scope grouping via
    the sidecar map, and the malformed-trace never-raises floor;
  * ``scope_map_from_hlo``: op_name metadata extraction plus the
    while-body majority-vote fallback for scan loops the compiler
    leaves untagged;
  * ``ProfilerSession`` cadence semantics (explicit supersteps /
    ``every`` / default) and the ResilientLoop begin/after handshake
    (capture at superstep N, no-op without a profiler);
  * ``build_profile_report`` + ``validate_profile_report`` on a
    synthetic capture bundle, ``compare_profile_reports`` regression
    detection, and the ``telemetry_from_config`` off-path pin for the
    new ``telemetry_profile_*`` knobs.

The real end-to-end capture during a training run is exercised by the
run_tests.sh observatory leg (and test_capture_during_tiny_ppo_run
below); everything else here is trace-fixture based so tier-1 stays
fast.
"""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

GOLDEN = Path(__file__).parent / "data" / "golden_profile.trace.json.gz"

GOLDEN_SCOPE_MAP = {
    "while.9": "rollout",
    "rollout_fusion": "rollout",
    "update_gemm_fusion": "update",
}


# ----------------------------------------------------------------------
# trace_parse: the golden fixture


def test_golden_trace_lane_split_and_aggregation():
    from gymfx_tpu.telemetry.trace_parse import parse_trace

    s = parse_trace(str(GOLDEN))
    assert s["ok"] and s["error"] is None
    assert s["device_lanes"] == ["/host:CPU/tf_XLATfrtCpuClient/1"]
    assert s["host_lanes"] == ["/host:CPU/python"]
    assert s["events"] == 8
    # device busy = union of the op intervals; window spans first start
    # to last stop (the 100us tail gap is host overhead)
    assert s["device_busy_us"] == pytest.approx(600.0)
    assert s["window_us"] == pytest.approx(700.0)
    # per-op totals are SELF time: the while container covers
    # [1000, 1300] but its two body thunks cover 200us of that
    assert s["ops"]["while.9"]["count"] == 1
    assert s["ops"]["while.9"]["total_us"] == pytest.approx(100.0)
    assert s["ops"]["rollout_fusion"]["count"] == 2
    assert s["ops"]["rollout_fusion"]["total_us"] == pytest.approx(200.0)
    assert s["ops"]["update_gemm_fusion"]["total_us"] == pytest.approx(250.0)
    assert s["ops"]["copy.1"]["total_us"] == pytest.approx(50.0)
    assert s["device_total_us"] == pytest.approx(600.0)
    # host side: the TraceAnnotation span and the dispatch frame
    assert s["host_ops"]["train/superstep"]["count"] == 1
    assert "PjitFunction" in s["host_ops"]


def test_golden_trace_scope_grouping_via_sidecar_map():
    from gymfx_tpu.telemetry.trace_parse import group_by_scope, parse_trace

    s = parse_trace(str(GOLDEN))
    g = group_by_scope(s, GOLDEN_SCOPE_MAP)
    assert g["rollout"] == pytest.approx(300.0)  # while self + fusions
    assert g["update"] == pytest.approx(250.0)
    assert g["unattributed"] == pytest.approx(50.0)  # the donation copy
    # no map at all: everything unattributed, nothing lost
    g0 = group_by_scope(s, None)
    assert g0["unattributed"] == pytest.approx(600.0)
    # full-path map values are reduced to their scope component
    g1 = group_by_scope(
        s, {"copy.1": "jit(train_step)/jit(main)/update/copy"}
    )
    assert g1["update"] == pytest.approx(50.0)


def test_args_scope_beats_sidecar_map(tmp_path):
    # TPU-style event: the op path rides in the event args and wins
    # over a (stale) sidecar entry
    from gymfx_tpu.telemetry.trace_parse import group_by_scope, parse_trace

    events = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 10, "name": "fusion.1",
         "args": {"long_name": "jit(train)/rollout/while/body/dot"}},
    ]
    p = tmp_path / "t.trace.json"
    p.write_text(json.dumps({"traceEvents": events}))
    s = parse_trace(str(p))
    assert s["ops"]["fusion.1"]["scope"] == "rollout"
    g = group_by_scope(s, {"fusion.1": "update"})
    assert g["rollout"] == pytest.approx(10.0) and g["update"] == 0.0


def test_malformed_traces_never_raise(tmp_path):
    from gymfx_tpu.telemetry.trace_parse import parse_trace

    # no files at all
    s = parse_trace(str(tmp_path))
    assert not s["ok"] and "no trace files" in s["error"]
    # truncated gzip
    bad = tmp_path / "x.trace.json.gz"
    bad.write_bytes(b"\x1f\x8b\x08\x00garbage")
    s = parse_trace(str(bad))
    assert not s["ok"] and s["events"] == 0
    # valid gzip, not JSON
    bad.write_bytes(gzip.compress(b"not json at all"))
    assert not parse_trace(str(bad))["ok"]
    # JSON but not a chrome trace: parses to an empty-but-ok summary
    ok_empty = tmp_path / "y.trace.json"
    ok_empty.write_text(json.dumps({"something": 1}))
    s = parse_trace(str(ok_empty))
    assert s["ok"] and s["events"] == 0 and s["device_busy_us"] == 0.0


# ----------------------------------------------------------------------
# scope_map_from_hlo

HLO_SNIPPET = """\
HloModule jit__train_step, entry_computation_layout={()->f32[]}

%region_1.10 (arg.1: f32[4]) -> f32[4] {
  %dot.3 = f32[4] dot(...), metadata={op_name="jit(_train_step_impl)/rollout/while/body/dot_general"}
  %add.4 = f32[4] add(...), metadata={op_name="jit(_train_step_impl)/rollout/while/body/add"}
}

%region_2.20 (arg.2: f32[4]) -> f32[4] {
  %dot.7 = f32[4] dot(...), metadata={op_name="jit(_train_step_impl)/update/minibatch/dot_general"}
}

ENTRY %main.30 (Arg_0.1: f32[4]) -> f32[] {
  %while.9 = (s32[], f32[4]) while(%tuple.1), condition=%region_0.5, body=%region_1.10
  %while.19 = (s32[], f32[4]) while(%tuple.2), condition=%region_0.6, body=%region_2.20
  %fusion.1 = f32[4] fusion(...), kind=kLoop, metadata={op_name="jit(_train_step_impl)/update/add"}
  %copy.3 = f32[4] copy(%Arg_0.1)
}
"""


# the function moved to telemetry/scopes.py (PR 26) and gives an OpScope
# (path, direction) per instruction; the observatory reads the path
SCOPE_MAP_CASES = {
    "ops under the rollout scope": ("dot.3", "rollout"),
    "ops under the rollout scope, second": ("add.4", "rollout"),
    "ops under the update scope, in a scan body": ("dot.7", "update"),
    "ops under the update scope, in the entry": ("fusion.1", "update"),
    # the scan `while` carries no op_name of its own: it inherits the
    # strict-majority scope of its body computation
    "the rollout scan inherits its body's scope": ("while.9", "rollout"),
    "the update scan inherits its body's scope": ("while.19", "update"),
    # the untagged copy stays out of the map (honestly unattributed)
    "an untagged copy stays out": ("copy.3", None),
}


@pytest.mark.parametrize("case", sorted(SCOPE_MAP_CASES))
@pytest.mark.parametrize("vocabulary", ["phases", "layers"])
def test_scope_map_from_hlo_metadata_and_while_bodies(case, vocabulary):
    from gymfx_tpu.telemetry.scopes import (
        PHASE_SCOPES,
        SCOPE_NAMES,
        scope_map_from_hlo,
    )

    # what the profiler asks for (the two phases) and the default (every
    # layer) agree where the text names no layer
    scopes = PHASE_SCOPES if vocabulary == "phases" else SCOPE_NAMES
    m = scope_map_from_hlo(HLO_SNIPPET, scopes=scopes)
    name, path = SCOPE_MAP_CASES[case]
    assert (m[name].path if name in m else None) == path
    assert m is not None and all(s.direction is None for s in m.values())


def test_scope_map_from_hlo_full_paths_and_garbage():
    from gymfx_tpu.telemetry.scopes import scope_map_from_hlo

    # scopes=None returns full op paths instead
    full = scope_map_from_hlo(HLO_SNIPPET, scopes=None)
    assert full["dot.3"].path.endswith("rollout/while/body/dot_general")
    # never raises on garbage
    assert scope_map_from_hlo(None) == {}
    assert scope_map_from_hlo("not hlo at all") == {}


def test_profiler_sidecar_holds_plain_scope_strings(tmp_path):
    """The capture bundle's scope_map.json stays {op: "rollout"|"update"}
    (what group_by_scope and the report read) now that scope_map_from_hlo
    gives (path, direction) pairs."""
    from gymfx_tpu.telemetry.profiler import SCOPE_MAP_NAME, ProfilerSession

    session = ProfilerSession(str(tmp_path), supersteps="0")
    session.set_workload_source(lambda it, k: {"hlo_text": HLO_SNIPPET})
    assert session.start_capture(0, 1)
    bundle = session.finish_capture()
    assert bundle is not None
    sidecar = json.loads((Path(bundle) / SCOPE_MAP_NAME).read_text())
    assert sidecar["while.9"] == "rollout" and sidecar["dot.7"] == "update"
    assert "copy.3" not in sidecar


# ----------------------------------------------------------------------
# ProfilerSession cadence semantics


def test_parse_supersteps_normalization():
    from gymfx_tpu.telemetry.profiler import _parse_supersteps

    assert _parse_supersteps(None) is None
    assert _parse_supersteps("") is None
    assert _parse_supersteps(False) is None
    assert _parse_supersteps(True) is None  # bool is not a superstep
    assert _parse_supersteps(3) == (3,)
    assert _parse_supersteps("1") == (1,)
    assert _parse_supersteps("8, 1,3") == (1, 3, 8)
    assert _parse_supersteps([5, 2]) == (2, 5)


def test_due_cadence(tmp_path):
    from gymfx_tpu.telemetry.profiler import ProfilerSession

    # explicit targets: due exactly when the window covers one
    p = ProfilerSession(str(tmp_path), supersteps="2,7")
    assert not p.due(0, 2) and p.due(2, 1) and p.due(0, 3)
    assert p.due(4, 4) and not p.due(8, 4)
    # every=N: first multiple of N inside the window
    p = ProfilerSession(str(tmp_path), supersteps="", every=4)
    assert p.due(0, 1)          # 0 is a multiple
    assert not p.due(1, 3)      # [1,4) misses 4
    assert p.due(1, 4)          # [1,5) covers 4
    assert p.due(8, 2) and not p.due(9, 2)
    # default when the dir is set but both cadence knobs unset:
    # one capture at superstep 1 (first post-compile dispatch)
    p = ProfilerSession(str(tmp_path))
    assert p.supersteps == (1,)
    assert not p.due(0, 1) and p.due(1, 1) and p.due(0, 2)


def test_resilient_loop_capture_handshake(tmp_path, monkeypatch):
    """begin_superstep opens the window at the due superstep,
    after_superstep closes it; without a profiler both are no-ops."""
    from gymfx_tpu.resilience.loop import ResilientLoop
    from gymfx_tpu.telemetry.profiler import ProfilerSession

    calls = []

    class FakeProfiler(ProfilerSession):
        def start_capture(self, it_start, k=1, **kw):
            due = self.due(it_start, k)
            calls.append(("start", it_start, k, due))
            self._active = {"it": it_start} if due else None
            return due

        def finish_capture(self):
            calls.append(("finish",))
            self._active = None
            return "bundle"

    prof = FakeProfiler(str(tmp_path), supersteps="1")
    loop = ResilientLoop(steps_per_iter=4, max_consecutive_skips=0,
                         profiler=prof)
    state_fn = lambda: ({}, None)  # noqa: E731
    for it in range(3):
        capturing = loop.begin_superstep(it, 1)
        assert capturing == (it == 1)
        loop.after_superstep(it, 1, {}, state_fn)
    assert calls == [
        ("start", 0, 1, False),
        ("start", 1, 1, True), ("finish",),
        ("start", 2, 1, False),
    ]
    # no profiler: begin_superstep is False and nothing is touched
    bare = ResilientLoop(steps_per_iter=4, max_consecutive_skips=0)
    assert bare.begin_superstep(0, 1) is False
    bare.after_superstep(0, 1, {}, state_fn)


def test_profiler_session_real_capture_writes_bundle(tmp_path):
    """A real (tiny) jax.profiler capture: bundle dir + manifest +
    ledger event + counter, scope map from a provided HLO payload."""
    import jax.numpy as jnp

    from gymfx_tpu.telemetry.ledger import RunLedger, read_ledger
    from gymfx_tpu.telemetry.profiler import ProfilerSession, find_captures
    from gymfx_tpu.telemetry.registry import MetricsRegistry

    reg = MetricsRegistry()
    ledger = RunLedger(str(tmp_path / "ledger.jsonl"))
    sess = ProfilerSession(
        str(tmp_path / "prof"), supersteps="0", config_sha256="abc",
        registry=reg, ledger=ledger,
    )
    sess.set_workload_source(lambda it, k: {
        "algo": "unit", "hlo_text": HLO_SNIPPET, "xla_flops_per_step": 10.0,
    })
    assert sess.start_capture(0, 1)
    assert sess.capturing
    (jnp.ones((16, 16)) @ jnp.ones((16, 16))).block_until_ready()
    bundle = sess.finish_capture()
    assert bundle is not None and not sess.capturing
    assert sess.captures == 1 and sess.capture_errors == 0
    assert find_captures(str(tmp_path / "prof")) == [bundle]

    manifest = json.loads((Path(bundle) / "manifest.json").read_text())
    assert manifest["config_sha256"] == "abc"
    assert manifest["it_start"] == 0 and manifest["k"] == 1
    assert manifest["algo"] == "unit"
    assert manifest["xla_flops_per_step"] == 10.0
    assert "platform" in manifest and "comparable" in manifest
    assert "fingerprints" in manifest
    assert manifest["scope_map_file"] == "scope_map.json"
    scope_map = json.loads((Path(bundle) / "scope_map.json").read_text())
    assert scope_map["while.9"] == "rollout"
    # the hlo payload itself must NOT land in the manifest
    assert "hlo_text" not in manifest

    rows = read_ledger(str(tmp_path / "ledger.jsonl"))
    caps = [r for r in rows if r["kind"] == "profile_capture"]
    assert len(caps) == 1 and caps[0]["path"] == bundle
    assert caps[0]["it_start"] == 0 and caps[0]["k"] == 1
    ledger.close()

    # the counter ticked and the age gauge is live
    from gymfx_tpu.telemetry import prometheus

    text = prometheus.render(reg)
    assert "gymfx_profile_captures_total 1" in text
    assert "gymfx_profile_last_capture_age_seconds" in text


def test_profiler_never_raises_on_bad_dir():
    from gymfx_tpu.telemetry.profiler import ProfilerSession

    sess = ProfilerSession("/dev/null/not/a/dir", supersteps="0")
    assert sess.start_capture(0, 1) is False
    assert sess.capture_errors == 1
    assert sess.finish_capture() is None  # nothing open: clean None


# ----------------------------------------------------------------------
# attribution: report build / validate / compare on a synthetic bundle


def _synthetic_bundle(tmp_path, *, k=1, manifest_extra=None):
    bundle = tmp_path / "capture_001_it1"
    bundle.mkdir(parents=True, exist_ok=True)
    (bundle / "synthetic.trace.json.gz").write_bytes(GOLDEN.read_bytes())
    manifest = {
        "schema_version": 1, "config_sha256": "deadbeef",
        "it_start": 1, "k": k, "it_end": 1 + k, "label": "unit",
        "platform": "cpu", "device_kind": "cpu", "comparable": False,
        "hw_flops_peak": None, "fingerprints": {"profile:unit|it1": "aa"},
        "scope_map_file": "scope_map.json",
        "xla_flops_per_step": 1000.0,
        "analytic_flops_per_step": 1500.0,
        # golden trace truth: rollout 300us, update 250us of 600us
        "phase_split": {"rollout_ms": 0.30, "update_ms": 0.25,
                        "iters": 2, "source": "measure_phase_split"},
    }
    manifest.update(manifest_extra or {})
    (bundle / "manifest.json").write_text(json.dumps(manifest))
    (bundle / "scope_map.json").write_text(json.dumps(GOLDEN_SCOPE_MAP))
    return bundle


def test_build_profile_report_attribution_and_mfu(tmp_path):
    from gymfx_tpu.telemetry.attribution import (
        build_profile_report,
        validate_profile_report,
    )

    report = build_profile_report(str(_synthetic_bundle(tmp_path)))
    assert validate_profile_report(report) == []
    t = report["trace"]
    assert t["ok"] and t["device_busy_ms"] == pytest.approx(0.6)
    assert t["window_ms"] == pytest.approx(0.7)
    assert t["dispatch_gap_ms"] == pytest.approx(0.1)
    assert t["dispatch_gap_frac"] == pytest.approx(1 / 7, abs=1e-3)
    # fusion coverage: 450us of fusion-named self time over 600us
    assert t["fusion_coverage"] == pytest.approx(0.75)
    p = report["phases"]
    assert p["rollout_ms"] == pytest.approx(0.3)
    assert p["update_ms"] == pytest.approx(0.25)
    assert p["rollout_frac"] == pytest.approx(300 / 550, abs=1e-3)
    assert p["attributed_frac"] == pytest.approx(550 / 600, abs=1e-3)
    r = report["reconciliation"]
    # trace 300/550 vs split 300/550: perfect agreement by construction
    assert r["split_rollout_frac"] == pytest.approx(300 / 550, abs=1e-3)
    assert r["rollout_frac_abs_err"] == pytest.approx(0.0, abs=1e-3)
    assert r["within_tolerance"] is True
    m = report["mfu_measured"]
    assert m["device_ms_per_step"] == pytest.approx(0.6)
    assert m["flops_per_step"] == 1000.0 and m["flops_source"] == "xla"
    assert m["achieved_flops_per_sec"] == pytest.approx(1000.0 / 0.0006,
                                                        rel=1e-3)
    assert m["mfu"] is None  # CPU: no public peak, null by convention
    assert report["mfu_analytic"]["analytic_flops_per_step"] == 1500.0
    # kernel rows carry the scope from the sidecar map
    scopes = {row["name"]: row["scope"] for row in t["top_kernels"]}
    assert scopes["rollout_fusion"] == "rollout"
    assert scopes["update_gemm_fusion"] == "update"
    assert scopes["copy.1"] is None


def test_build_profile_report_k_divides_per_step(tmp_path):
    from gymfx_tpu.telemetry.attribution import build_profile_report

    report = build_profile_report(str(_synthetic_bundle(tmp_path, k=2)))
    assert report["mfu_measured"]["device_ms_per_step"] == pytest.approx(0.3)
    rows = {r["name"]: r for r in report["trace"]["top_kernels"]}
    assert rows["rollout_fusion"]["total_ms_per_step"] == pytest.approx(0.1)


def test_build_profile_report_on_broken_bundle_never_raises(tmp_path):
    from gymfx_tpu.telemetry.attribution import (
        build_profile_report,
        validate_profile_report,
    )

    report = build_profile_report(str(tmp_path / "nothing_here"))
    assert validate_profile_report(report) == []
    assert report["trace"]["ok"] is False
    assert report["phases"]["rollout_frac"] is None
    assert report["reconciliation"]["within_tolerance"] is None
    assert report["mfu_measured"]["device_ms_per_step"] is None


def test_compare_profile_reports_gates_kernel_regressions(tmp_path):
    from gymfx_tpu.telemetry.attribution import (
        build_profile_report,
        compare_profile_reports,
    )

    base = build_profile_report(str(_synthetic_bundle(tmp_path)))
    # identical reports: clean pass, comparable
    verdict = compare_profile_reports(base, base)
    assert verdict["ok"] and verdict["comparable"]
    assert verdict["regressions"] == []
    # inflate one kernel past the threshold: must fail
    import copy

    slow = copy.deepcopy(base)
    for row in slow["trace"]["top_kernels"]:
        if row["name"] == "update_gemm_fusion":
            row["total_ms_per_step"] *= 1.5
    verdict = compare_profile_reports(base, slow, threshold=0.25)
    assert not verdict["ok"]
    assert [r["name"] for r in verdict["regressions"]] == [
        "update_gemm_fusion"
    ]
    # below-noise kernels are skipped entirely
    verdict = compare_profile_reports(base, slow, threshold=0.25, min_ms=10.0)
    assert verdict["ok"]
    # speedups report as improvements, not regressions
    verdict = compare_profile_reports(slow, base, threshold=0.25)
    assert verdict["ok"] and any(
        r["name"] == "update_gemm_fusion" for r in verdict["improvements"]
    )


def test_profile_report_cli_report_and_compare(tmp_path, capsys):
    import sys

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
    from profile_report import main as cli_main

    bundle = _synthetic_bundle(tmp_path)
    assert cli_main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "Profile report" in out and "rollout" in out
    report_path = bundle / "profile_report.json"
    assert report_path.exists()
    # compare: same report against itself passes…
    assert cli_main(["--compare", str(report_path), str(report_path)]) == 0
    # …and a synthetic kernel regression must fail
    report = json.loads(report_path.read_text())
    for row in report["trace"]["top_kernels"]:
        row["total_ms_per_step"] = (row["total_ms_per_step"] or 0) * 2
    slow_path = tmp_path / "slow.json"
    slow_path.write_text(json.dumps(report))
    assert cli_main(["--compare", str(report_path), str(slow_path)]) == 1


# ----------------------------------------------------------------------
# config wiring: the off path stays off


def test_profile_knobs_unset_keep_telemetry_none():
    from gymfx_tpu.config.defaults import DEFAULT_VALUES
    from gymfx_tpu.telemetry import telemetry_from_config

    assert telemetry_from_config(dict(DEFAULT_VALUES)) is None
    # cadence knobs alone (no dir) do NOT construct telemetry: the dir
    # is the master switch
    cfg = dict(DEFAULT_VALUES)
    cfg["telemetry_profile_supersteps"] = "1,2"
    cfg["telemetry_profile_every"] = 4
    assert telemetry_from_config(cfg) is None


def test_profile_dir_constructs_profiler(tmp_path):
    from gymfx_tpu.telemetry import telemetry_from_config

    tel = telemetry_from_config({
        "telemetry_profile_dir": str(tmp_path / "prof"),
        "telemetry_profile_supersteps": "0,2",
        "telemetry_profile_every": 8,
    })
    assert tel is not None and tel.profiler is not None
    assert tel.profiler.supersteps == (0, 2)
    assert tel.profiler.every == 8
    assert tel.profiler.config_sha256  # stamped from the config digest
    tel.close()


def test_ledger_schema_knows_profile_capture():
    from gymfx_tpu.telemetry.ledger import EVENT_KINDS, load_ledger_schema

    assert "profile_capture" in EVENT_KINDS
    schema = load_ledger_schema()
    assert schema["kinds"]["profile_capture"]["required"] == [
        "path", "it_start", "k"
    ]


@pytest.mark.slow
def test_capture_during_tiny_ppo_run(tmp_path):
    """End-to-end: a 3-superstep PPO run with the knobs set captures
    superstep 1, and the bundle renders a schema-valid report."""
    from gymfx_tpu.config.defaults import DEFAULT_VALUES
    from gymfx_tpu.telemetry.attribution import (
        build_profile_report,
        validate_profile_report,
    )
    from gymfx_tpu.telemetry.profiler import find_captures
    from gymfx_tpu.train.ppo import train_from_config

    cfg = dict(DEFAULT_VALUES)
    cfg.update({
        "input_file": "tests/data/eurusd_uptrend.csv",
        "window_size": 8, "num_envs": 4, "ppo_horizon": 16,
        "ppo_epochs": 2, "ppo_minibatches": 2,
        "policy_kwargs": {"hidden": [16, 16]},
        "train_total_steps": 192, "seed": 1,
        "telemetry_profile_dir": str(tmp_path / "prof"),
    })
    train_from_config(cfg)
    caps = find_captures(str(tmp_path / "prof"))
    assert len(caps) == 1 and caps[0].endswith("it1")
    report = build_profile_report(caps[0])
    assert validate_profile_report(report) == []
    assert report["trace"]["ok"] and report["trace"]["events"] > 0
    assert report["phases"]["attributed_frac"] > 0.5
    assert report["mfu_measured"]["device_ms_per_step"] > 0
    assert report["mfu_measured"]["flops_per_step"] > 0
