"""``--rehearse`` of the cell PR 35 added, ``lfm2moe_w1024_train``, traced and
untraced, at the configuration's tiny ``rehearse`` widths on the CPU (the five
layers whole: a dense convolution layer, three convolution + expert layers, one
grouped-query + expert layer; window 64), read from ``BENCHMARK.json``.  Each run
is a process of its own, as the driver's are.  Run by hand:
``python -m pytest benchmarks/tests -q`` (not part of tier-1; about four minutes)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "lfm2moe_w1024_train"
CONFIG = ROOT / "benchmarks/configs/ppo_lfm2moe_ep8_bf16.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
# shares of a peak: a CPU has none in peaks.json, so a rehearsal leaves them out
OF_A_PEAK = ("mfu", "roofline")
NEW_METRICS = {"short_conv_block_device_ms", "policy_mfu.conv_hybrid_decoder"}


def names(kind):
    return {m["name"] for m in BENCHMARK[kind] if CELL in m.get("workloads", [CELL])}


def bench_modules():
    sys.path[:0] = [str(ROOT / "benchmarks")]
    try:
        import harness
        import run as bench_run
    finally:
        del sys.path[0]
    return harness, bench_run


def run_cell(trace):
    return subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 35), "--seconds", "2", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=1500)


def test_the_cell_is_in_the_benchmark_with_its_configuration_and_traffic():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ppo_lfm2moe_ep8_bf16", "m1q_16x4", 1)
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == cell["config"])
    conf = json.loads((ROOT / entry["file"]).read_text())
    assert entry["source"] == conf["source"]
    assert sorted(entry["reduced"]) == sorted(conf["reduced"]) == sorted(conf["published"]) == [
        "num_dense_layers", "num_experts", "num_hidden_layers", "vocab_size"]
    assert {"env_steps_per_s", "setup_s"} <= names("end_to_end")
    assert NEW_METRICS | {
        "attention_block_device_ms", "ffn_block_device_ms", "moe_router_device_ms",
        "moe_dispatch_device_ms", "moe_experts_device_ms", "grouped_matmul_roofline",
        "grouped_matmul_dw_roofline", "scope_coverage"} <= names("per_layer")
    # no shared expert, no latent attention (that reader wants q_lora_rank), no KDA layer
    assert not {"moe_shared_device_ms", "policy_mfu", "linear_attention_block_device_ms",
                "policy_mfu.hybrid_decoder"} & names("per_layer")


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog beside the guide here")
def test_every_number_of_the_catalog_row_is_in_the_file_under_its_key():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "LFM2-24B-A2B")
    conf = json.loads(CONFIG.read_text())
    assert conf["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if conf.get(k, "absent") != v)
    assert differs == sorted(conf["reduced"])
    assert {k: row["config"][k] for k in conf["reduced"]} == conf["published"]
    kwargs, pub = conf["program"]["policy_kwargs"], row["config"]
    assert (kwargs["hidden_size"], kwargs["num_attention_heads"], kwargs["num_key_value_heads"],
            kwargs["conv_L_cache"], kwargs["intermediate_size"], kwargs["moe_intermediate_size"],
            kwargs["n_routed_experts"], kwargs["num_experts_per_tok"], kwargs["norm_topk_prob"],
            kwargs["routed_scaling_factor"], kwargs["rms_norm_eps"], kwargs["rope_theta"]) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["num_key_value_heads"],
        pub["conv_L_cache"], pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["num_experts"], pub["num_experts_per_tok"], pub["norm_topk_prob"],
        pub["routed_scaling_factor"], pub["norm_eps"], pub["rope_parameters"]["rope_theta"])
    # the layers held: a leading dense convolution layer, then published layers 3-6
    assert kwargs["layer_types"] == pub["layer_types"][:1] + pub["layer_types"][3:7]
    assert kwargs["n_shared_experts"] == 0 and (kwargs["experts_held"], kwargs["n_layers"]) == (
        conf["num_experts"], conf["num_hidden_layers"])


def test_the_required_operations_come_from_the_files_alone():
    harness, bench_run = bench_modules()
    rooflines = harness.load_module("rooflines", "conv_hybrid_decoder")
    cell = bench_run.load_cell(CELL, False)
    sizes = rooflines.sizes(cell)
    assert rooflines.layer_kinds(sizes) == (4, 1, 1, 4)
    parts = rooflines.forward_flops_per_token(sizes)
    # four layers x (2048 x 6144 in, three taps a channel, 2048 x 2048 out)
    assert parts["conv_projections"] == 2.0 * 4 * (2048 * 6144 + 3 * 2048 + 2048 * 2048)
    # q and o 2048 x 2048 each, k and v 2048 x 512 each
    assert parts["gqa_projections"] == 2.0 * (2 * 2048 * 2048 + 2 * 2048 * 512)
    # 32 query heads, q.k and p.v over 64 dims, 512.5 keys met on average
    assert parts["attention_scores"] == 2.0 * 32 * 128 * 512.5
    assert parts["dense_ffn"] == 2.0 * 3 * 2048 * 11776
    assert parts["experts"] == 2.0 * 4 * 3 * 2048 * 1536 * 4 * 8 / 64
    assert parts["shared"] == 0.0
    assert 342e6 < sum(parts.values()) < 344e6          # ISSUE 35: 343 MFLOP a token
    total = rooflines.train_step_flops(cell)
    assert total == 16 * 4 * 1024 * sum(parts.values()) * 4
    assert 89e12 < total < 91e12                         # ISSUE 35: 89.9 TFLOP a step
    # the accepted grouped-product reader counts this cell's calls from the same keys
    trunk = harness.load_module("rooflines", "mla_moe_decoder")
    calls = trunk.grouped_matmul_calls(cell)
    assert [c for c, _, _ in calls["grouped_matmul"]] == [20, 20, 48, 48]
    assert [c for c, _, _ in calls["grouped_matmul_dw"]] == [16, 16]


def test_the_new_readers_read_a_trace_and_say_nothing_without_one(monkeypatch):
    harness, bench_run = bench_modules()
    cell = bench_run.load_cell(CELL, False)
    rooflines = harness.load_module("rooflines", "conv_hybrid_decoder")
    mfu = harness.load_module("layer_metrics", "policy_mfu.conv_hybrid_decoder")
    block = harness.load_module("layer_metrics", "short_conv_block_device_ms")
    bare = {"cell": cell, "counters": {"train_steps": 3}, "trace": {}}
    assert mfu.read(bare) is None and block.read(bare) is None       # no trace
    busy = rooflines.train_step_flops(cell) / 197e12 / 0.25           # a quarter of the peak
    traced = {**bare, "trace": {"busy_s": 3 * busy, "device_ops": []}}
    assert mfu.read(traced) is None                                   # a CPU has no peak
    monkeypatch.setattr(
        harness, "load_module", lambda folder, name, load=harness.load_module: (
            rooflines if (folder, name) == ("rooflines", "conv_hybrid_decoder")
            else load(folder, name)))
    monkeypatch.setattr(rooflines, "device_peak", lambda: {"bf16_flops_per_s": 197e12})
    assert mfu.read(traced) == pytest.approx(25.0)
    import scope_times
    table = {"seconds": {("update/loss/policy_forward/short_conv", "bwd"): 0.5,
                         ("rollout/policy_act/short_conv", ""): 0.25,
                         ("update/loss/policy_forward/attention", "fwd"): 0.125}}
    monkeypatch.setattr(scope_times, "table_of", lambda run: table)
    assert block.read(traced) == pytest.approx(750.0)
    monkeypatch.setattr(scope_times, "table_of", lambda run: {"seconds": {
        ("update/loss/policy_forward/attention", "fwd"): 0.125}})
    assert block.read(traced) is None         # the parent commit's program: no such scope


def test_the_mixers_parameter_groups_are_judged_with_the_others():
    harness, _ = bench_modules()
    check = harness.load_module("checks", "reference_policy_conv_hybrid")
    groups = check._hybrid._base.GROUPS
    assert {"conv_in", "conv_taps", "conv_out", "gqa_qkv", "gqa_out", "gqa_head_norms",
            "dense_ffn", "experts", "router"} <= set(groups)
    # the benchmark's copy of the reference is the program's file, below its header
    copy = (ROOT / "benchmarks/checks/conv_hybrid_decoder_reference.py").read_text()
    program = (ROOT / "gymfx_tpu/reference/hybrid_decoder.py").read_text()
    assert copy.split("text.\n\n", 1)[1] == program[3:]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_line(trace):
    done = run_cell(trace)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(l) for l in done.stdout.strip().splitlines() if l.startswith("{")]
    line = lines[-1]
    check = next(l["note"]["check"] for l in lines if "check" in l.get("note", {}))
    assert line["correct"] is True, check
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"      # never a device number
    wanted = names("per_layer" if trace else "end_to_end")
    assert {n for n in wanted if not any(p in n for p in OF_A_PEAK)} == set(line["metrics"])
    assert check["kind"] == "reference_policy" and not check["over_limit"]
    assert check["twin_params_max_abs_diff"] == check["twin_loss_max_abs_diff"] == 0.0
    assert check["same_rollout"] and check["minibatches"] == 4
    assert (check["decisions"], check["tokens_per_decision"]) == (16, 64)
    # the counters on the check's note line
    assert 0.0 < check["short_conv_gate_rms"] and 0.0 < check["moe_held_share"] < 1.0
    assert check["moe_load_max_over_mean"] >= 1.0
    assert 0 < check["update_rel_l2"] < check["controls"]["first_minibatch_only"]["update_rel_l2"]
    assert {"conv_in", "conv_taps", "conv_out", "gqa_qkv", "gqa_out",
            "gqa_head_norms"} <= set(check["update_rel_l2_by_group"])
    # what the limits have to refuse goes through them as the program does, and is refused
    assert {"float8_e4m3fn", "conv_no_mixing", "kv_head_by_modulo", "no_qk_norm",
            "first_minibatch_only", "unchanged"} <= set(check["controls"])
    assert all(read["refused_by"] for read in check["controls"].values()), check["controls"]
    # the head norms left out hide in the forward (random weights behind an RMSNorm give a
    # head's dims unit variance already) and show in the update: a norm that is held and
    # not applied gets no gradient, Adam leaves it, its group reads a state left unchanged
    no_norm = check["controls"]["no_qk_norm"]
    assert no_norm["update_rel_l2_by_group"]["gqa_head_norms"] == 1.0
    assert no_norm["refused_by"] == ["update_rel_l2_worst"]
    assert check["update_rel_l2_by_group"]["gqa_head_norms"] < 0.5
    # every limit of the TIMED size names a distance the check reads (one it does not
    # read would make every run on the chip incorrect)
    timed = json.loads(CONFIG.read_text())
    assert set(timed["check"]["limits"]) <= set(check)
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        scopes = next(l["note"]["scope_ms"]["scopes"] for l in lines
                      if "scope_ms" in l.get("note", {}))
        for part in ("short_conv", "attention", "ffn", "moe_router", "moe_dispatch",
                     "moe_experts"):
            assert f"rollout/policy_act/{part}" in scopes
            assert f"update/loss/policy_forward/{part}" in scopes
        assert not [path for path in scopes if path.endswith("moe_shared")]
        assert line["metrics"]["short_conv_block_device_ms"]["value"] > 0
