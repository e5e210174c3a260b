"""Device time per part of a block (part_times.py, PR 37): the four readers on
a hand-made step and a hand-made window, nothing from a program without the
part map, and on a trace recorded on the CPU (record_scoped_trace.py's
``--rehearse``: a tiny ``transformer_ring`` PPO step, two steps) the part
map's seconds add up to the busy time, op for op.  A traced ``--rehearse`` of
``transformer_w256_train`` prints the ``part_ms`` note line once and the four
metrics of its lists.  Run by hand: ``python -m pytest benchmarks/tests -q``
(not part of tier-1)."""
import gzip
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
sys.path[:0] = [str(HERE.parent), str(ROOT), str(HERE)]

import harness  # noqa: E402
import part_times  # noqa: E402
import scope_times  # noqa: E402
from gymfx_tpu.telemetry import scopes  # noqa: E402

READERS = ["kda_scan_device_ms", "causal_conv_device_ms", "attention_core_device_ms",
           "unscoped_device_ms"]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
# one instruction a part, one a layer, one the rule names, one nothing names
STEP = """\
HloModule jit_step

ENTRY %main.1 (a: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  %scan.1 = f32[4]{0} dot(%p.1, %p.1), metadata={op_name="jit(s)/update/loss/jvp(policy_forward)/M/linear_attention/kda/kda_scan/dot_general"}
  %taps.1 = f32[4]{0} add(%p.1, %p.1), metadata={op_name="jit(s)/rollout/policy_act/M/short_conv/conv/causal_conv/add"}
  %core.1 = f32[4]{0} add(%p.1, %p.1), metadata={op_name="jit(s)/update/loss/transpose(jvp(policy_forward))/M/attention/attention_core/add"}
  %proj.1 = f32[4]{0} dot(%p.1, %p.1), metadata={op_name="jit(s)/update/loss/jvp(policy_forward)/M/linear_attention/kda/dot_general"}
  %copy-start.1 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%proj.1)
  %copy-done.1 = f32[4]{0} copy-done(%copy-start.1)
  %use.1 = f32[4]{0} add(%copy-done.1, %p.1), metadata={op_name="jit(s)/update/loss/jvp(policy_forward)/M/linear_attention/kda/add"}
  ROOT %copy.1 = f32[4]{0} copy(%use.1), metadata={op_name="jit(s)/jit(h)/copy"}
}
"""
OPS = [["scan.1", 0.4], ["taps.1", 0.1], ["core.1", 0.2], ["proj.1", 0.3],
       ["copy-done.1", 0.05], ["copy.1", 0.02], ["use.1", 0.01]]


class Executable:
    def as_text(self):
        return STEP


@pytest.fixture
def handed_out(monkeypatch):
    monkeypatch.setattr(part_times, "_tables", {})
    scopes.register_step(Executable())
    return {"trace": {"xplane": "window.xplane.pb", "device_ops": OPS},
            "counters": {"train_steps": 2}}


@pytest.mark.parametrize("reader, want", [
    ("kda_scan_device_ms", 200.0), ("causal_conv_device_ms", 50.0),
    ("attention_core_device_ms", 100.0), ("unscoped_device_ms", 10.0)])
def test_each_reader_reads_its_parts_ms_a_step(handed_out, reader, want):
    got = harness.load_module("layer_metrics", reader).read(handed_out)
    assert got == pytest.approx(want, rel=1e-12)


def test_the_note_is_printed_once_and_adds_up(handed_out, capsys):
    for reader in READERS:
        harness.load_module("layer_metrics", reader).read(handed_out)
    notes = [json.loads(line)["note"]["part_ms"] for line in capsys.readouterr().out.splitlines()]
    assert len(notes) == 1
    note = notes[0]
    assert note["all_paths_ms"] == pytest.approx(note["busy_ms_per_step"], rel=1e-12)
    assert note["parts"]["update/loss/policy_forward/linear_attention/kda_scan"] == {
        "fwd": pytest.approx(200.0)}
    assert note["unnamed_ms"] == pytest.approx(10.0)
    assert note["largest_unnamed"] == [["copy.1", "copy", pytest.approx(10.0)]]
    assert set(note["build"]) == {"scope_map_s", "part_map_s", "text_bytes"}


@pytest.mark.parametrize("reader", READERS)
def test_a_reader_reads_nothing_without_a_trace_or_a_part_map(handed_out, reader, monkeypatch):
    module = harness.load_module("layer_metrics", reader)
    assert module.read({"trace": {}, "counters": {"train_steps": 2}}) is None
    monkeypatch.delattr(scopes, "last_step_part_map")       # a program from before PR 37
    assert module.read(handed_out) is None
    monkeypatch.setattr(part_times, "_tables", {})
    monkeypatch.setitem(sys.modules, "gymfx_tpu.telemetry.scopes", None)   # ... or PR 26
    assert module.read(handed_out) is None


def test_the_new_metrics_are_listed_where_the_program_has_their_parts():
    lists = {m["name"]: m["workloads"] for m in BENCHMARK["per_layer"] if m["name"] in READERS}
    cells = [w["name"] for w in BENCHMARK["workloads"]]
    assert lists["kda_scan_device_ms"] == ["ling3flash_w1024_train"]
    assert lists["causal_conv_device_ms"] == ["ling3flash_w1024_train", "lfm2moe_w1024_train"]
    assert lists["attention_core_device_ms"] == [c for c in cells if c != "mlp_flagship_train"]
    assert lists["unscoped_device_ms"] == cells


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A CPU trace of two tiny steps (record_scoped_trace.py --rehearse), with
    the part map of the step it traced."""
    import record_scoped_trace

    out = tmp_path_factory.mktemp("parted")
    assert record_scoped_trace.main(str(out), True) == 0
    xplane = out / "scoped.xplane.pb"
    with gzip.open(out / "scoped.xplane.pb.gz", "rb") as packed, open(xplane, "wb") as raw:
        shutil.copyfileobj(packed, raw)
    expected = json.loads((out / "scoped.expected.json").read_text())
    return (scope_times.op_seconds(xplane), scopes.last_step_part_map(),
            scopes.last_step_scope_map(), expected)


def test_on_a_recorded_cpu_trace_the_parts_add_up_to_the_busy_time(recorded):
    ops, part_map, scope_map, expected = recorded
    table = scope_times.build(ops, part_map, expected["steps"])
    layers = scope_times.build(ops, scope_map, expected["steps"])
    assert sum(table["seconds"].values()) == pytest.approx(table["busy_s"], rel=1e-9)
    # the recorder's own table of the same window (the CPU client's op events
    # overlap on its threads: their union, ``busy_s``, is a chip's sum only)
    assert 1e3 * table["busy_s"] == pytest.approx(
        expected["scope_ms"]["busy_ms_per_step"], rel=1e-9)
    core = scope_times.total(table, last=scopes.ATTENTION_CORE)
    assert 0 < core < scope_times.total(layers, last=scopes.ATTENTION)
    # a part only parts its layer: the layer map's instructions, by their part
    # paths, fill every layer as they filled it; the rule only adds to them
    same = scope_times.build(ops, {name: part_map[name] for name in scope_map},
                             expected["steps"])
    for layer in scopes.LAYERS:
        assert scope_times.total(same, layer) == pytest.approx(
            scope_times.total(layers, layer), rel=1e-9, abs=1e-15), layer
    assert part_times.unnamed(table) <= part_times.unnamed(layers)


def test_a_traced_rehearsal_prints_the_note_and_the_cells_metrics():
    cell = "transformer_w256_train"
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload", cell,
         "--seed", str(2**31 + 37), "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    notes = {key: [line["note"][key] for line in lines if key in line.get("note", {})]
             for key in ("part_ms", "scope_ms")}
    assert len(notes["part_ms"]) == len(notes["scope_ms"]) == 1
    part, scope = notes["part_ms"][0], notes["scope_ms"][0]
    metrics = {k: v["value"] for k, v in lines[-1]["metrics"].items()}
    assert {"attention_core_device_ms", "unscoped_device_ms"} <= set(metrics)
    assert "kda_scan_device_ms" not in metrics
    assert part["all_paths_ms"] == pytest.approx(part["busy_ms_per_step"], rel=1e-9)
    assert 0 < metrics["attention_core_device_ms"] <= metrics["attention_block_device_ms"]
    no_scope = sum(scope["scopes"].get(scope_times.NO_SCOPE, {}).values())
    assert metrics["unscoped_device_ms"] <= no_scope + 1e-9
