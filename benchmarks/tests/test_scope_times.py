"""Device time per layer of the fused step (scope_times.py) against one small
trace of a REAL train step recorded on the chip with its scope map beside it
(``data/scoped.*``, made by record_scoped_trace.py on "TPU v5 lite": a tiny
``transformer_ring`` PPO step, both env-dynamics kernels on, two steps).
Run by hand: ``python -m pytest benchmarks/tests -q`` (not part of tier-1)."""
import gzip
import json
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent.parent)]

import reduce_trace  # noqa: E402
import scope_times  # noqa: E402
from gymfx_tpu.telemetry import scopes  # noqa: E402

DATA = HERE / "data"
EXPECTED = json.loads((DATA / "scoped.expected.json").read_text())
FORWARD = scopes.join(scopes.UPDATE, scopes.LOSS, scopes.POLICY_FORWARD)


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    path = tmp_path_factory.mktemp("scoped") / "scoped.xplane.pb"
    with gzip.open(DATA / "scoped.xplane.pb.gz", "rb") as packed, open(path, "wb") as raw:
        shutil.copyfileobj(packed, raw)
    return path


@pytest.fixture(scope="module")
def table(xplane):
    scope_map = {name: tuple(scope) for name, scope in
                 json.loads((DATA / "scoped.scope_map.json").read_text()).items()}
    return scope_times.build(scope_times.op_seconds(xplane), scope_map,
                             EXPECTED["steps"])


def test_the_phases_and_the_rest_sum_to_the_reductions_busy_time(xplane, table):
    # the same events, summed two ways: by scope here, as a union there
    reduced = reduce_trace.reduce(xplane)
    assert reduced["busy_s"] == pytest.approx(EXPECTED["busy_s"], rel=1e-9)
    per_step = reduced["busy_s"] / EXPECTED["steps"]
    parts = (scope_times.total(table, scopes.ROLLOUT)
             + scope_times.total(table, scopes.UPDATE)
             + scope_times.outside_phases(table))
    assert parts == pytest.approx(per_step, rel=1e-3)
    assert table["busy_s"] == pytest.approx(per_step, rel=1e-3)


@pytest.mark.parametrize("parent", [
    scopes.ROLLOUT, scopes.UPDATE, scopes.join(scopes.ROLLOUT, scopes.ENV_STEP),
    scopes.join(scopes.ROLLOUT, scopes.POLICY_ACT),
    scopes.join(scopes.UPDATE, scopes.LOSS), FORWARD])
def test_a_scope_is_its_children_and_its_own_time(table, parent):
    depth = len(parent.split("/"))
    children = {"/".join(path.split("/")[:depth + 1]) for path, _way in table["seconds"]
                if path.startswith(parent + "/")}
    own = sum(s for (path, _way), s in table["seconds"].items() if path == parent)
    below = sum(scope_times.total(table, child) for child in children)
    assert children and below > 0
    assert scope_times.total(table, parent) == pytest.approx(own + below, rel=1e-9)


def test_forward_and_backward_split_the_loss_and_nothing_else(table):
    loss = scopes.join(scopes.UPDATE, scopes.LOSS)
    fwd = scope_times.total(table, loss, direction=scopes.FWD)
    bwd = scope_times.total(table, loss, direction=scopes.BWD)
    assert 0 < fwd < bwd
    assert fwd + bwd == pytest.approx(scope_times.total(table, loss), rel=1e-9)
    for path, way in table["seconds"]:
        assert (way != "") == (path == loss or path.startswith(loss + "/")), path
    # the backward kernel's op path repeats `loss` (a custom VJP): still bwd
    kernels = {name: seconds for name, seconds
               in table["ops"][scopes.join(FORWARD, scopes.ATTENTION)]
               if name.endswith(scope_times.KERNEL_SUFFIX)}
    assert {name.split(".")[0] for name in kernels} == {
        scopes.KERNEL_ATTENTION_FWD, scopes.KERNEL_ATTENTION_BWD}


def test_the_blocks_are_found_in_both_phases_and_hold_the_attention_kernels(table):
    attention = scope_times.total(table, last=scopes.ATTENTION)
    by_phase = sum(scope_times.total(table, scopes.join(under, scopes.ATTENTION))
                   for under in (scopes.join(scopes.ROLLOUT, scopes.POLICY_ACT), FORWARD))
    assert attention == pytest.approx(by_phase, rel=1e-9)
    assert 0 < scope_times.total(table, last=scopes.FFN) < attention
    kernel_s = sum(s for ops in table["ops"].values() for name, s in ops
                   if name.endswith(scope_times.KERNEL_SUFFIX))
    assert kernel_s == pytest.approx(EXPECTED["kernel_s"] / EXPECTED["steps"], rel=1e-6)
    dynamics = dict(table["ops"][scopes.join(scopes.ROLLOUT, scopes.ENV_STEP, scopes.DYNAMICS)])
    for kernel in (scopes.KERNEL_FILL_BRACKETS, scopes.KERNEL_MARK_REWARD):
        assert any(name.startswith(kernel + ".") for name in dynamics), kernel


def test_the_remainder_is_reported_and_small(table):
    outside = scope_times.outside_phases(table)
    unscoped = sum(s for (path, _way), s in table["seconds"].items() if path == "")
    assert 0 < unscoped == pytest.approx(outside, rel=1e-9)   # every path is rooted
    assert all(name.split(".")[0].split("-")[0] in ("copy", "slice", "bitcast", "while")
               for name, _s in table["ops"][""][:5]), table["ops"][""][:5]
    covered = scope_times.in_a_layer(table) / table["busy_s"]
    assert 0.9 < covered < 1.0
    groups = sum(s for (path, _way), s in table["seconds"].items()
                 if path in scopes.GROUP_SCOPES)
    assert covered * table["busy_s"] + groups + outside == pytest.approx(
        table["busy_s"], rel=1e-9)


def test_the_note_is_what_the_recorder_looked_over(table):
    note = json.loads(json.dumps(scope_times.note(table)))
    assert note["scopes"].keys() == EXPECTED["scope_ms"]["scopes"].keys()
    assert note["busy_ms_per_step"] == pytest.approx(
        EXPECTED["scope_ms"]["busy_ms_per_step"], rel=1e-9)
    for path, row in EXPECTED["scope_ms"]["scopes"].items():
        assert note["scopes"][path] == pytest.approx(row, rel=1e-9), path
    assert len(note["largest_ops"]) == 3
    assert all(len(ops) <= 10 for ops in note["largest_ops"].values())


def test_a_run_without_a_trace_or_a_program_without_scopes_reads_nothing(xplane, monkeypatch):
    assert scope_times.ms({"trace": {}, "counters": {"train_steps": 2}}, "rollout") is None
    run = {"trace": {"xplane": str(xplane)}, "counters": {"train_steps": 2}}
    monkeypatch.setattr(scopes, "_executable", None)
    monkeypatch.setattr(scopes, "_scope_map", None)
    assert scope_times.ms(run, "rollout") is None        # no step was handed out
    monkeypatch.setitem(sys.modules, "gymfx_tpu.telemetry.scopes", None)
    monkeypatch.setattr(scope_times, "_tables", {})
    assert scope_times.ms(run, "rollout") is None        # a program from before PR 26
