"""``--rehearse`` of the cell PR 33 added, ``ling3flash_w1024_train``, traced
and untraced, at the configuration's tiny ``rehearse`` widths on the CPU (the
six-layer pattern whole: five Kimi-Delta-Attention layers, one latent-attention
layer; window 64 = four chunks of 16), read from ``BENCHMARK.json``.  Each run
is a process of its own, as the driver's are.  Run by hand:
``python -m pytest benchmarks/tests -q`` (not part of tier-1; about six minutes)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "ling3flash_w1024_train"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
# shares of a peak: a CPU has none in peaks.json, so a rehearsal leaves them out
OF_A_PEAK = ("mfu", "roofline")
NEW_METRICS = {"linear_attention_block_device_ms", "policy_mfu.hybrid_decoder"}


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
         "--seed", str(2**31 + 33), "--seconds", "2", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=1500)


def test_the_cell_is_in_the_benchmark_with_its_configuration_and_traffic():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ppo_ling3flash_ep64_bf16", "m1q_4x8", 1)
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == cell["config"])
    conf = json.loads((ROOT / entry["file"]).read_text())
    assert entry["source"] == conf["source"]
    assert sorted(entry["reduced"]) == sorted(conf["reduced"]) == sorted(conf["published"]) == [
        "first_k_dense_replace", "num_experts", "num_hidden_layers", "vocab_size"]
    assert {"env_steps_per_s", "setup_s"} <= names("end_to_end")
    assert NEW_METRICS | {"attention_block_device_ms", "ffn_block_device_ms",
                          "moe_router_device_ms", "moe_experts_device_ms"} <= names("per_layer")
    assert "policy_mfu" not in names("per_layer")       # that reader knows no KDA layer


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog beside the guide here")
def test_every_number_of_the_catalog_row_is_in_the_file_under_its_key():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Ling-3.0-flash-VL")
    conf = json.loads((ROOT / "benchmarks/configs/ppo_ling3flash_ep64_bf16.json").read_text())
    assert conf["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if conf.get(k, "absent") != v)
    assert differs == sorted(conf["reduced"])
    assert {k: row["config"][k] for k in conf["reduced"]} == conf["published"]
    kwargs, pub = conf["program"]["policy_kwargs"], row["config"]
    assert (kwargs["hidden_size"], kwargs["num_attention_heads"], kwargs["kda_head_dim"],
            kwargs["kv_lora_rank"], kwargs["qk_nope_head_dim"], kwargs["qk_rope_head_dim"],
            kwargs["v_head_dim"], kwargs["intermediate_size"], kwargs["moe_intermediate_size"],
            kwargs["n_routed_experts"], kwargs["num_experts_per_tok"], kwargs["n_group"],
            kwargs["topk_group"], kwargs["kda_conv_size"], kwargs["layer_group_size"],
            kwargs["kda_lower_bound"], kwargs["routed_scaling_factor"], kwargs["rope_theta"],
            kwargs["rms_norm_eps"], kwargs["q_lora_rank"]) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["head_dim"], pub["kv_lora_rank"],
        pub["qk_nope_head_dim"], pub["qk_rope_head_dim"], pub["v_head_dim"],
        pub["intermediate_size"], pub["moe_intermediate_size"], pub["num_experts"],
        pub["num_experts_per_tok"], pub["n_group"], pub["topk_group"],
        pub["short_conv_kernel_size"], pub["layer_group_size"], pub["kda_lower_bound"],
        pub["routed_scaling_factor"], pub["rope_theta"], pub["rms_norm_eps"], pub["q_lora_rank"])


def test_the_required_operations_come_from_the_files_alone_and_count_the_scan_as_a_solve():
    harness, bench_run = bench_modules()
    rooflines = harness.load_module("rooflines", "hybrid_decoder")
    cell = bench_run.load_cell(CELL, False)
    sizes = rooflines.sizes(cell)
    assert rooflines.layer_kinds(sizes) == (5, 1, 1, 5)
    parts = rooflines.forward_flops_per_token(sizes)
    # five layers x (six 2560 x 4096 products + beta + three four-tap convolutions)
    assert parts["kda_projections"] == 2.0 * 5 * (6 * 2560 * 4096 + 2560 * 32 + 3 * 4 * 4096)
    # a head and token: 64 columns of scores twice over 128, the same for the solve and the
    # scores' product, and three 128 x 128 products with the state
    assert parts["kda_scan"] == 2.0 * 5 * 32 * (64 * 256 + 3 * 128 * 128)
    assert parts["experts"] == 2.0 * 5 * 3 * 2560 * 768 * 8 * 8 / 512
    total = rooflines.train_step_flops(cell)
    assert total == 4 * 8 * 1024 * sum(parts.values()) * 4
    assert 110e12 < total < 125e12


def test_the_new_readers_read_a_trace_and_say_nothing_without_one(monkeypatch):
    harness, bench_run = bench_modules()
    cell = bench_run.load_cell(CELL, False)
    rooflines = harness.load_module("rooflines", "hybrid_decoder")
    mfu = harness.load_module("layer_metrics", "policy_mfu.hybrid_decoder")
    block = harness.load_module("layer_metrics", "linear_attention_block_device_ms")
    bare = {"cell": cell, "counters": {"train_steps": 3}, "trace": {}}
    assert mfu.read(bare) is None and block.read(bare) is None       # no trace
    busy = rooflines.train_step_flops(cell) / 197e12 / 0.25           # a quarter of the peak
    traced = {**bare, "trace": {"busy_s": 3 * busy, "device_ops": []}}
    assert mfu.read(traced) is None                                   # a CPU has no peak
    monkeypatch.setattr(
        harness, "load_module", lambda folder, name, load=harness.load_module: (
            rooflines if (folder, name) == ("rooflines", "hybrid_decoder")
            else load(folder, name)))
    monkeypatch.setattr(rooflines, "device_peak", lambda: {"bf16_flops_per_s": 197e12})
    assert mfu.read(traced) == pytest.approx(25.0)
    # the scope reader: a table with the scope's time in it, and one without the scope
    import scope_times
    table = {"seconds": {("update/loss/policy_forward/linear_attention", "bwd"): 0.5,
                         ("rollout/policy_act/linear_attention", ""): 0.25,
                         ("update/loss/policy_forward/attention", "fwd"): 0.125}}
    monkeypatch.setattr(scope_times, "table_of", lambda run: table)
    assert block.read(traced) == pytest.approx(750.0)
    monkeypatch.setattr(scope_times, "table_of", lambda run: {"seconds": {
        ("update/loss/policy_forward/attention", "fwd"): 0.125}})
    assert block.read(traced) is None         # the parent commit's program: no such scope


def test_the_size_of_an_update_is_judged_apart_from_its_direction():
    import numpy as np

    harness, _ = bench_modules()
    check = harness.load_module("checks", "reference_policy_hybrid")
    before = {"in_proj": np.zeros((8, 8), np.float32), "router": np.zeros((8,), np.float32)}
    want = {"in_proj": np.ones((8, 8), np.float32), "router": np.ones((8,), np.float32)}
    turned = {"in_proj": np.where(np.arange(64).reshape(8, 8) % 4 == 0, -1.0, 1.0)
              .astype(np.float32), "router": want["router"]}
    read = check.update_distance(turned, want, before)      # a quarter of the signs turned
    assert read["update_rel_l2"] == pytest.approx((4 * 16 / 72) ** 0.5)
    assert read["update_norm_shortfall"] == pytest.approx(0.0, abs=1e-6)
    eighth = {k: v / 8 for k, v in want.items()}            # one minibatch of eight
    assert check.update_distance(eighth, want, before)["update_norm_shortfall"] == (
        pytest.approx(0.875))
    assert check.update_distance(before, want, before)["update_norm_shortfall"] == 1.0
    assert {"kda_qkv", "kda_out"} <= set(check._base.GROUPS)


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
    assert check["same_rollout"] and check["minibatches"] == 8
    assert (check["decisions"], check["tokens_per_decision"]) == (32, 64)
    assert -5.0 < check["kda_log_decay_mean"] < 0.0
    assert 0 < check["update_rel_l2"] < check["controls"]["first_minibatch_only"]["update_rel_l2"]
    # the linear-attention layer's groups are judged with the others
    assert {"kda_qkv", "kda_conv", "kda_decay", "kda_beta", "kda_out", "mla_q",
            "head_gate"} <= set(check["update_rel_l2_by_group"])
    # what the limits have to refuse goes through them as the program does, and is refused
    assert {"no_decay", "beta_one", "no_shared_expert", "unscaled_weights", "float8_e4m3fn",
            "first_minibatch_only", "unchanged"} <= set(check["controls"])
    assert all(read["refused_by"] for read in check["controls"].values()), check["controls"]
    # the SIZE of the change is judged apart from its direction: sign-like first updates of
    # Adam turn with a small error of the gradient, their size does not
    first = check["controls"]["first_minibatch_only"]
    assert check["update_norm_shortfall"] < 0.1 < 0.5 < first["update_norm_shortfall"]
    assert "update_norm_shortfall" in first["refused_by"]
    # every limit of the TIMED size names a distance the check reads (one it does not
    # read would make every run on the chip incorrect)
    timed = json.loads((ROOT / "benchmarks/configs/ppo_ling3flash_ep64_bf16.json").read_text())
    assert set(timed["check"]["limits"]) <= set(check)
    assert timed["check"]["limits"]["update_norm_shortfall"] < first["update_norm_shortfall"]
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        scopes = next(l["note"]["scope_ms"]["scopes"] for l in lines
                      if "scope_ms" in l.get("note", {}))
        for part in ("linear_attention", "attention", "ffn", "moe_router", "moe_experts"):
            assert f"rollout/policy_act/{part}" in scopes
            assert f"update/loss/policy_forward/{part}" in scopes
        assert line["metrics"]["linear_attention_block_device_ms"]["value"] > 0
