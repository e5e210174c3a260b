"""Required operations of the hybrid decoder trunk's train step (linear-attention
layers beside latent attention, ``policy_kwargs.layer_group_size`` > 0), from the
configuration and traffic files alone (the cell as ``run.load_cell`` read it).
Kept with the benchmark: what ``policy_mfu.hybrid_decoder`` divides by may not
move with the program.

Counted: the matrix products of the share of the model this chip holds at the
EXPECTED routing (as ``rooflines/mla_moe_decoder.py`` counts them), causal
attention of the latent-attention layers (a token at position t meets t + 1
keys), and of a linear-attention layer its six projections, beta, the short
convolutions and the gated delta rule AS A CHUNKED TRIANGULAR SOLVE at the
published chunk of ``SCAN_CHUNK`` positions: per token and head the two
intra-chunk score rows (a token meets the (C - 1) / 2 earlier and the (C + 1) / 2
earlier-or-own positions of its chunk over the key width), the forward
substitution of its pseudo-value and the scores' product with the pseudo-values
(the same counts over the value width), and three key x value products with the
carried state (read for the pseudo-value, read for the output, update).  That is
REQUIRED work: an implementation that inverts the triangle by repeated squaring,
pads a chunk, or walks position by position does other work, and none of it is
counted.  Norms, RoPE, softmax, gates' elementwise parts, the router's top-k,
dispatch and the optimizer are not counted: a share computed here is a lower
bound of the work done.  One train step = one rollout forward of every collected
decision + a forward and a backward (twice a forward) of every sample in each
epoch; recomputation in the backward pass is NOT counted.
"""
from __future__ import annotations

SCAN_CHUNK = 64


import harness

_trunk = harness.load_module("rooflines", "mla_moe_decoder")
sizes = _trunk.sizes                # the cell's program and traffic sizes, by key
device_peak = _trunk.device_peak    # peaks.json's entry of the device, None on a CPU


def layer_kinds(s: dict):
    """(linear-attention layers, latent-attention layers, dense layers, expert layers)."""
    layers, period = s["n_layers"], s.get("layer_group_size") or 0
    latent = sum(1 for l in range(layers) if not period or (l + 1) % period == 0)
    dense = min(s.get("first_k_dense_replace", 1), layers)
    return layers - latent, latent, dense, layers - dense


def forward_flops_per_token(s: dict) -> dict:
    """Operations of one token's forward through the share held, by part."""
    h, heads = s["hidden_size"], s["num_attention_heads"]
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    linear, latent, dense, sparse = layer_kinds(s)
    held = s.get("experts_held") or s["n_routed_experts"]
    expert = 3 * h * s["moe_intermediate_size"]
    d = s.get("kda_head_dim", 128)
    inner, taps = heads * d, s.get("kda_conv_size", 4)
    c = SCAN_CHUNK
    q_path = (h * s["q_lora_rank"] + s["q_lora_rank"] * heads * qk if s.get("q_lora_rank")
              else h * heads * qk)
    mla = (q_path + h * (s["kv_lora_rank"] + s["qk_rope_head_dim"])
           + s["kv_lora_rank"] * heads * (s["qk_nope_head_dim"] + s["v_head_dim"])
           + heads * s["v_head_dim"] * h + (h * heads if s.get("attn_output_gate") else 0))
    keys_met = (s["window"] + 1) / 2
    scan = heads * (((c - 1) / 2 + (c + 1) / 2) * (d + d) + 3 * d * d)
    return {
        "kda_projections": 2.0 * linear * (6 * h * inner + h * heads + 3 * taps * inner),
        "kda_scan": 2.0 * linear * scan,
        "mla_projections": 2.0 * latent * mla,
        "attention_scores": 2.0 * latent * heads * (qk + s["v_head_dim"]) * keys_met,
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
