"""Required operations of the train step of the decoder trunk whose blocks take
their mixer by the published ``layer_types`` (gated short convolutions beside
grouped-query attention, an expert layer without a shared expert), from the
configuration and traffic files alone (the cell as ``run.load_cell`` read it).
Kept with the benchmark: what ``policy_mfu.conv_hybrid_decoder`` divides by may
not move with the program.

Counted: the matrix products of the share of the model this chip holds at the
EXPECTED routing (as ``rooflines/mla_moe_decoder.py`` counts them): of a
convolution layer its projection to three times the width, its taps and its
projection back; of a grouped-query layer its q, k, v and output products and its
causal scores (a token at position t meets t + 1 keys: ``(W + 1) / 2`` on
average, over the head width for q.k and again for p.v, every query head its
own); the dense layer; the router; the routed experts held, active choices only;
a shared expert where ``n_shared_experts`` says there is one.  Norms, RoPE, the
softmax, the gates' elementwise products, the router's top-k, dispatch and the
optimizer are not counted: a share computed here is a lower bound of the work
done.  One train step = one rollout forward of every collected decision (the
bootstrap value's forward is left out) + a forward and a backward (twice a
forward) of every sample in each epoch; recomputation in the backward pass is NOT
counted.
"""
from __future__ import annotations

import harness

_trunk = harness.load_module("rooflines", "mla_moe_decoder")
sizes = _trunk.sizes                # the cell's program and traffic sizes, by key
device_peak = _trunk.device_peak    # peaks.json's entry of the device, None on a CPU


def layer_kinds(s: dict):
    """(convolution layers, grouped-query layers, dense layers, expert layers)."""
    kinds = list(s["layer_types"])
    dense = min(s.get("first_k_dense_replace", 1), len(kinds))
    return (kinds.count("conv"), kinds.count("full_attention"), dense, len(kinds) - dense)


def forward_flops_per_token(s: dict) -> dict:
    """Operations of one token's forward through the share held, by part."""
    h, heads, kv_heads = s["hidden_size"], s["num_attention_heads"], s["num_key_value_heads"]
    d = h // heads
    conv, full, dense, sparse = layer_kinds(s)
    held = s.get("experts_held") or s["n_routed_experts"]
    expert = 3 * h * s["moe_intermediate_size"]
    keys_met = (s["window"] + 1) / 2
    return {
        "conv_projections": 2.0 * conv * (h * 3 * h + s["conv_L_cache"] * h + h * h),
        "gqa_projections": 2.0 * full * (2 * h * heads * d + 2 * h * kv_heads * d),
        "attention_scores": 2.0 * full * heads * 2 * d * keys_met,
        "dense_ffn": 2.0 * dense * 3 * h * s["intermediate_size"],
        "router": 2.0 * sparse * h * s["n_routed_experts"],
        "experts": 2.0 * sparse * expert * s["num_experts_per_tok"] * held
                   / s["n_routed_experts"],
        "shared": 2.0 * sparse * expert * s.get("n_shared_experts", 0),
    }


def train_step_flops(cell: dict) -> float:
    s = sizes(cell)
    tokens = s["envs"] * s["horizon"] * s["window"]
    return tokens * sum(forward_flops_per_token(s).values()) * (1 + 3 * s["epochs"])
