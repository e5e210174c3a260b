"""Required operations and bytes of the ``mla_moe_decoder`` train step and of
the grouped-product kernels in it, from the configuration and traffic files
alone (the cell as ``run.load_cell`` read it).  Kept with the benchmark:
what a roofline share or ``policy_mfu`` divides by may not move with the
program.

Counted: the matrix products of the share of the model this chip holds, at
the EXPECTED routing (every token sends ``num_experts_per_tok`` choices over
``n_routed_experts``, of which ``experts_held`` are here: active experts
only; the two kernels' own shares take the rows the traced steps really
had, ``traced_held_share``), and causal attention (a token at position t meets t + 1 keys).
Norms, RoPE, the softmax, the router's top-k, dispatch and the optimizer
are not counted: a share computed here is a lower bound of the work done.
One train step = one rollout forward of every collected decision (the
bootstrap value's forward, one more in ``ppo_horizon``, is left out) + a
forward and a backward (twice a forward) of every sample in each epoch.
Recomputation in the backward pass is NOT counted in the step; it IS
counted in a kernel's own calls, which it really makes.
"""
from __future__ import annotations


def sizes(cell: dict) -> dict:
    program = {**cell["config"]["program"], **cell["traffic"]["program"]}
    return {**program["policy_kwargs"], "window": int(program["window_size"]),
            "envs": int(program["num_envs"]), "horizon": int(program["ppo_horizon"]),
            "epochs": int(program["ppo_epochs"]),
            "minibatches": int(program["ppo_minibatches"])}


def forward_flops_per_token(s: dict) -> dict:
    """Operations of one token's forward through the share held, by part."""
    h, heads = s["hidden_size"], s["num_attention_heads"]
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    layers = s["n_layers"]
    dense = min(s.get("first_k_dense_replace", 1), layers)
    sparse = layers - dense
    held = s.get("experts_held") or s["n_routed_experts"]
    expert = 3 * h * s["moe_intermediate_size"]
    projections = (h * s["q_lora_rank"] + s["q_lora_rank"] * heads * qk
                   + h * (s["kv_lora_rank"] + s["qk_rope_head_dim"])
                   + s["kv_lora_rank"] * heads * (s["qk_nope_head_dim"] + s["v_head_dim"])
                   + heads * s["v_head_dim"] * h)
    keys_met = (s["window"] + 1) / 2
    return {
        "mla_projections": 2.0 * layers * projections,
        "attention_scores": 2.0 * layers * heads * (qk + s["v_head_dim"]) * keys_met,
        "dense_ffn": 2.0 * dense * 3 * h * s["intermediate_size"],
        "router": 2.0 * sparse * h * s["n_routed_experts"],
        "experts": 2.0 * sparse * expert * s["num_experts_per_tok"] * held
                   / s["n_routed_experts"],
        "shared": 2.0 * sparse * expert * s.get("n_shared_experts", 1),
    }


def train_step_flops(cell: dict) -> float:
    s = sizes(cell)
    tokens = s["envs"] * s["horizon"] * s["window"]
    return tokens * sum(forward_flops_per_token(s).values()) * (1 + 3 * s["epochs"])


def grouped_matmul_calls(cell: dict, held_share=None) -> dict:
    """{kernel: [(calls a train step, operations a call, bytes a call)]} of
    the two Mosaic kernels: per expert layer the gate|up and the down
    product, in every rollout forward (``ppo_horizon`` + 1 of them: the
    kernel runs in the bootstrap forward too), in the update's forward, its
    recomputation and (against the transposed weights) its backward; the
    weight-gradient kernel once per product in the backward.  Rows in use:
    ``held_share`` of the token choices (the program's counter
    ``moe_held_share``), the expected routing's where none is given; bytes:
    the rows read and written (bfloat16) and each expert's weights once."""
    s = sizes(cell)
    h, f = s["hidden_size"], s["moe_intermediate_size"]
    held = s.get("experts_held") or s["n_routed_experts"]
    sparse = s["n_layers"] - min(s.get("first_k_dense_replace", 1), s["n_layers"])
    if held_share is None:
        held_share = held / s["n_routed_experts"]
    share = s["num_experts_per_tok"] * held_share
    products = ((h, 2 * f), (f, h))
    rollout_tokens = s["envs"] * s["window"]
    update_tokens = rollout_tokens * s["horizon"] // s["minibatches"]
    passes = {
        "grouped_matmul": [(rollout_tokens, (s["horizon"] + 1) * sparse),
                           (update_tokens, 3 * s["minibatches"] * s["epochs"] * sparse)],
        "grouped_matmul_dw": [(update_tokens, s["minibatches"] * s["epochs"] * sparse)],
    }
    out = {}
    for kernel, runs in passes.items():
        out[kernel] = []
        for tokens, calls in runs:
            rows = tokens * share
            for k, n in products:
                flops = 2.0 * rows * k * n
                moved = 2.0 * (rows * k + rows * n + held * k * n)
                out[kernel].append((calls, flops, moved))
    return out


def kernel_least_seconds(cell: dict, kernel: str, peak: dict, held_share=None) -> float:
    """The least time a train step's calls of ``kernel`` could take on a
    chip with ``peak``: each call the larger of operations over peak FLOP/s
    and bytes over peak bytes/s."""
    return sum(calls * max(flops / peak["bf16_flops_per_s"], moved / peak["hbm_bytes_per_s"])
               for calls, flops, moved in grouped_matmul_calls(cell, held_share)[kernel])


def traced_held_share(run: dict):
    """The program's counter ``moe_held_share`` (the share of token choices
    that fell on the experts held here, the mean of a step's minibatches)
    over the steps of the traced window: what the kernels' rows really were.
    With random weights it is far from the expected routing's 12.5 % and moves
    with the seed and with how full the windows are (PERF.md section 6), and
    ``runners/train.py`` drops the step's metrics.  So the cell's check
    (``checks/reference_policy.py``) has its twin of the timed trainer take
    the steps a traced run takes (warm-up, the phase split's rollout-update
    pairs, the window) and leaves the window's mean in
    ``harness.traced_counters``.  ``None`` where no check left one."""
    import harness

    return (getattr(harness, "traced_counters", None) or {}).get("moe_held_share")


def device_peak():
    """peaks.json's entry of the device JAX runs on, or ``None`` where the
    device is not in the table (the CPU of a rehearsal)."""
    import json
    from pathlib import Path

    import jax

    table = json.loads((Path(__file__).resolve().parent.parent / "peaks.json").read_text())
    return table.get(jax.devices()[0].device_kind)


def kernel_seconds(run: dict, kernel: str):
    """Device seconds a train step of the traced window spent in the Mosaic
    calls named ``kernel`` (``kernel.<n>``), or ``None`` without a trace or
    without such a call."""
    trace, steps = run.get("trace") or {}, run.get("counters", {}).get("train_steps")
    if not trace.get("device_ops") or not steps:
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.split(" ")[0].rsplit(".", 1)[0] == kernel
                  or name.split(" ")[0] == kernel)
    return seconds / steps if seconds else None


def kernel_roofline_share(run: dict, kernel: str):
    peak, seconds = device_peak(), kernel_seconds(run, kernel)
    if peak is None or seconds is None:
        return None
    held_share = traced_held_share(run)
    if held_share is None:
        return None
    return 100.0 * kernel_least_seconds(run["cell"], kernel, peak, held_share) / seconds
