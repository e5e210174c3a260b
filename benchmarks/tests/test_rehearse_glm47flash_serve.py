"""``--rehearse`` of the serving cell ``glm47flash_serve_w256`` at its configuration's
tiny ``rehearse`` widths on the CPU (three layers: the dense one and two expert layers,
4 of 16 experts held; window 32; ladder 1, 4), read from ``BENCHMARK.json``: the
runner, the check and its controls, the four per-layer readers, and what the runner
builds on (weights made by the benchmark and served in bfloat16, the engine's answers
against the policy's own forward).  Each run is a process of its own, as the
benchmark's runs are.  Run by hand: ``python -m pytest benchmarks/tests -q`` (not part
of tier-1; about two minutes)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "glm47flash_serve_w256"
CONFIG = ROOT / "benchmarks/configs/serve_glm47flash_ep8_bf16.json"
CATALOG = os.environ.get("MODEL_CATALOG")
SEED = 2**31 + 42
NEW_PER_LAYER = {"queue_wait_p95_ms", "batch_occupancy", "device_idle_share.serve",
                 "serve_policy_mfu"}


def names(kind):
    return {m["name"] for m in BENCHMARK[kind] if CELL in m.get("workloads", [CELL])}


def bench_modules():
    sys.path[:0] = [str(ROOT / "benchmarks"), str(ROOT)]
    try:
        import harness
        import run as bench_run
    finally:
        del sys.path[:2]
    return harness, bench_run


def run_cell(trace, script="run.py", code=None):
    args = ["--workload", CELL, "--seed", str(SEED), "--seconds", "2", "--trace",
            str(trace), "--rehearse"]
    command = ([sys.executable, "-c", code, *args] if code
               else [sys.executable, str(ROOT / "benchmarks" / script), *args])
    return subprocess.run(command, cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=600)


def parsed(done):
    lines = [json.loads(l) for l in done.stdout.strip().splitlines() if l.startswith("{")]
    check = next(l["note"]["check"] for l in lines if "check" in l.get("note", {}))
    return lines[-1], check


def test_the_cell_is_in_the_benchmark_with_its_configuration_and_traffic():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "serve_glm47flash_ep8_bf16", "poisson_w256_knee90", 1)
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == cell["config"])
    conf = json.loads((ROOT / entry["file"]).read_text())
    assert entry["source"] == conf["source"]
    assert sorted(entry["reduced"]) == sorted(conf["reduced"]) == sorted(conf["published"]) == [
        "n_routed_experts", "num_nextn_predict_layers", "vocab_size"]
    # every layer held: no depth cut
    kwargs = conf["program"]["policy_kwargs"]
    assert kwargs["n_layers"] == conf["num_hidden_layers"] == 47
    assert names("end_to_end") == {"decision_p95_ms", "decisions_per_s", "setup_s"}
    assert names("per_layer") == NEW_PER_LAYER
    traffic = json.loads((ROOT / "benchmarks/traffic/poisson_w256_knee90.json").read_text())
    assert isinstance(traffic["rate"], (int, float)) and traffic["sessions"] == 1024


@pytest.mark.skipif(not (CATALOG and Path(CATALOG).is_file()), reason="MODEL_CATALOG names no file")
def test_every_number_of_the_catalog_row_is_in_the_file_under_its_key():
    row = next(r for r in map(json.loads, Path(CATALOG).read_text().splitlines())
               if r["name"] == "GLM-4.7-Flash")
    conf = json.loads(CONFIG.read_text())
    assert conf["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if conf.get(k, "absent") != v)
    assert differs == sorted(conf["reduced"])
    assert {k: row["config"][k] for k in conf["reduced"]} == conf["published"]


def test_the_required_operations_of_a_decision_come_from_the_files_alone(monkeypatch):
    harness, bench_run = bench_modules()
    cell = bench_run.load_cell(CELL, False)
    rooflines = harness.load_module("rooflines", "mla_moe_decoder")
    program = cell["config"]["program"]
    sizes = {**program["policy_kwargs"], "window": program["window_size"]}
    parts = rooflines.forward_flops_per_token(sizes)
    assert 3.60e9 < sum(parts.values()) < 3.62e9                   # 3,609 MFLOP a token
    assert 0.56 < parts["mla_projections"] / sum(parts.values()) < 0.58
    mfu = harness.load_module("layer_metrics", "serve_policy_mfu")
    run = {"cell": cell, "counters": {"decisions_traced": 100}, "trace": {}}
    assert mfu.read(run) is None                                   # no trace
    busy = 100 * 256 * sum(parts.values()) / 197e12 / 0.25         # a quarter of the peak
    run["trace"] = {"busy_s": busy}
    assert mfu.read(run) is None                                   # a CPU has no peak
    monkeypatch.setattr(
        harness, "load_module", lambda folder, name, load=harness.load_module: (
            rooflines if (folder, name) == ("rooflines", "mla_moe_decoder")
            else load(folder, name)))
    monkeypatch.setattr(rooflines, "device_peak", lambda: {"bf16_flops_per_s": 197e12})
    assert mfu.read(run) == pytest.approx(25.0)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_line(trace):
    done = run_cell(trace)
    assert done.returncode == 0, done.stderr[-3000:]
    line, check = parsed(done)
    assert line["correct"] is True, check
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"      # never a device number
    wanted = names("per_layer" if trace else "end_to_end")
    # a CPU has no peak in peaks.json: the share of one is left out
    assert wanted - {"serve_policy_mfu"} == set(line["metrics"])
    assert check["kind"] == "served_decoder" and not check["over_limit"]
    assert check["decisions"] == 8 and len(check["sample"]) == 8
    # the numbers compared end the line and stderr, each beside its limit
    assert list(line)[-1] == "compared"
    limits = bench_modules()[1].load_cell(CELL, True)["config"]["check"]["limits"]
    assert set(line["compared"]) == set(limits)
    assert done.stderr.strip().splitlines()[-len(limits):] == [
        f"compared {n} {r['value']} limit {r['limit']}" for n, r in line["compared"].items()]
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert line["breakdown"]["device_ops"] and line["breakdown"]["idle_gaps"]


def test_every_control_is_refused_by_the_limits():
    done = run_cell(0, script="check_controls.py")
    assert done.returncode == 0, done.stderr[-3000:]
    line, check = parsed(done)
    assert line["correct"] is True, check
    controls = check["controls"]
    assert set(controls) == {"float8", "experts_float8", "dropped_expert", "shifted_windows"}
    assert all(read["refused_by"] for read in controls.values()), controls
    # a window one bar off is refused by its tokens alone, whatever the trunk makes of it
    assert "tokens_max_abs_diff" in controls["shifted_windows"]["refused_by"]


HALF_BATCH = """
import sys
sys.path[:0] = ["benchmarks", "."]
import check_controls, run
load_cell = run.load_cell

def loaded(*args, **kwargs):
    cell = load_cell(*args, **kwargs)
    cell["traffic"]["rate"] = 600.0     # batches of two to four form on a CPU
    return cell

run.load_cell = loaded
sys.exit(check_controls.main(["--fault", "half_batch", *sys.argv[1:]]))
"""


def test_half_of_each_batch_left_out_is_not_correct():
    """The back half of every batch answered with the mean of the front half's
    answers (``check_controls.half_batch``), at a load where batches form:
    ``correct`` is false, and the sampled decisions' rows show the batches."""
    done = run_cell(0, code=HALF_BATCH)
    assert done.returncode == 0, done.stderr[-3000:]
    line, check = parsed(done)
    rows = check["per_decision"]["row"]
    assert rows is not None and max(rows) >= 1, check["per_decision"]
    assert line["correct"] is False and check["over_limit"], check


ALTERED = """
import sys
sys.path[:0] = ["benchmarks", "."]
import numpy as np
from gymfx_tpu.serve import engine
decide = engine.InferenceEngine.decide_batch

def altered(self, *args, **kwargs):
    out = decide(self, *args, **kwargs)
    return out._replace(actor_out=np.asarray(out.actor_out) + np.float32(0.25))

engine.InferenceEngine.decide_batch = altered
import run
sys.exit(run.main(sys.argv[1:]))
"""


def test_an_answer_altered_where_it_is_produced_is_not_correct():
    done = run_cell(0, code=ALTERED)
    assert done.returncode == 0, done.stderr[-3000:]
    line, check = parsed(done)
    assert line["correct"] is False and check["over_limit"], check


def test_the_benchmarks_balancing_rule_is_the_programs():
    """``decoder_weights.balanced_bias`` gives the choice bias the program's
    ``mla_moe_decoder.balanced_choice_bias`` gives, on the same scores."""
    import jax
    import numpy as np

    from gymfx_tpu.train.mla_moe_decoder import Dims, balanced_choice_bias

    sys.path[:0] = [str(ROOT / "benchmarks"), str(ROOT)]
    try:
        import decoder_weights
    finally:
        del sys.path[:2]
    kwargs = json.loads(CONFIG.read_text())["program"]["policy_kwargs"]
    dims = Dims(**{k: v for k, v in kwargs.items() if k in Dims._fields})
    rng = np.random.default_rng(3)
    scores = jax.nn.sigmoid(rng.standard_normal((512, dims.n_routed_experts)).astype(np.float32))
    bias = (0.02 * rng.standard_normal(dims.n_routed_experts)).astype(np.float32)
    ours = decoder_weights.balanced_bias(scores, bias, decoder_weights.reference_cfg(kwargs))
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(balanced_choice_bias(scores, bias, dims)))
    assert not np.array_equal(np.asarray(ours), bias)


def test_bfloat16_weights_serve_the_bits_of_the_same_weights_in_float32():
    """The engine's answers are the policy's own jitted forward, window by
    window (six windows: a bucket of four and one of two), and the weights the
    benchmark holds in bfloat16 give that forward bit for bit what the same
    values held in float32 give (the forward casts every such matrix to the
    compute dtype)."""
    code = """
import json, sys, time
sys.path[:0] = ["benchmarks", "."]
import jax, jax.numpy as jnp, numpy as np
import harness, run
from runners.serve_window import Stack
cell = run.load_cell("glm47flash_serve_w256", True)
stack = Stack(harness.Context(cell=cell, seed=7, seconds=1.0, trace=False, rehearse=True,
                              t0=time.perf_counter(), devices=jax.devices()[:1]))
windows = stack.windows_at(np.arange(40, 46))
policy, params = stack.engine.policy, stack.params
wide = jax.tree.map(lambda a: a.astype(jnp.float32), params)
dtypes = sorted({str(a.dtype) for a in jax.tree.leaves(params)})
forward = jax.jit(policy.apply)
own = [forward(params, w[None]) for w in windows]
served = stack.engine.decide_batch(windows)
held32 = [forward(wide, w[None]) for w in windows]
stack.close()
print(json.dumps({
    "dtypes": dtypes,
    "served_is_own": bool(np.array_equal(served.actor_out, np.concatenate([o[0] for o in own]))
                          and np.array_equal(served.value, np.concatenate([o[1] for o in own]))),
    "bf16_is_f32": all(np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
                       for a, b in zip(own, held32))}))
"""
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True,
                          text=True, timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-3000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert out == {"dtypes": ["bfloat16", "float32"], "served_is_own": True,
                   "bf16_is_f32": True}
