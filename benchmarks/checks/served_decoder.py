"""``correct`` for a cell that serves a stateless ``mla_moe_decoder`` trunk:
the answers the timed window served, against the plain reference
(``checks/mla_moe_decoder_reference.py``, float32, ``highest``).

The object compared is the object timed.  Once the window has closed, the
runner draws from ``--seed`` a sample of ``decisions`` of the requests due in the
window and answered, and keeps what the engine served them (the actor's
logits, the value and the greedy action), the window each carried and the bar
that window ends at.  The serving stack is then freed.  The reference's own
tokens are built from the tape by the benchmark (``checks/window_tokens.py``),
and the reference runs over them with the same weights, made by the benchmark
(``decoder_weights.py``) and held in bfloat16 as the engine holds them, each
layer's upcast to float32 when its turn comes: the 47-layer tree is never
whole in float32.  Distances taken (``distances``); those the spec's
``limits`` name are judged, the others printed:

  tokens_max_abs_diff    the largest |token the engine was sent - token the
                         benchmark builds for that bar|: the program's
                         encoding and the window's place on the tape
  logit_median_abs_diff  the median over the sample of each decision's largest
                         |served logit - reference logit|
  value_median_abs_diff  the median of |served value - reference value|
  logit_p90_abs_diff     the 90th percentile over the sample of each decision's
                         largest logit gap: a fault that reaches a tenth of the
                         decisions or more (a bucket's later rows answered
                         wrongly) moves it, where it moves the median little
  value_p90_abs_diff     the same of the value gaps
  logit_max_abs_diff     the largest |served logit - reference logit|
  logit_rel_l2           |served logits - reference logits| / |reference logits|
                         over the sample
  value_max_abs_diff     the largest |served value - reference value|
  value_rel_l2           the same as logit_rel_l2, of the values
  action_gap             the largest (reference's best logit - the reference's
                         logit of the action served): 0 where every served
                         action is the reference's greedy one

With random weights a routing choice flips, somewhere in the trunk, for some
windows, and a flip moves that one decision's outputs by tenths: the largest of
a sample is such a flip under any precision, its median and its 90th
percentile are not (the readings: the configuration's ``check.why``).  The
detail also gives each sampled decision's gaps and, where the runner could
read them from the batcher's records, the size of the batch it was served in
and its row there (``per_decision``).

With ``run_controls`` in the spec (off in the configuration file;
``benchmarks/check_controls.py`` and ``benchmarks/tests`` turn it on) the same
distances are taken of what the limits have to refuse, each the reference put
in the engine's place against the reference: ``float8`` (every product's
operands rounded to float8_e4m3fn, the precision next below the
configuration's bfloat16), ``experts_float8`` (that in the expert layers'
products only), ``dropped_expert`` (the term of held expert 0 left out of every
expert layer), ``shifted_windows`` (every window one bar later than the one
sent, its tokens built by the benchmark).
"""
from __future__ import annotations

import harness

CONTROLS = ("float8", "experts_float8", "dropped_expert", "shifted_windows")


def forward_by_layer(params, tokens, cfg: dict, block: int, variant=None):
    """Reference logits (B, actions) and values (B,) of ``tokens`` (B, W,
    token_dim), ``block`` windows at a time, one layer at a time, float32 at
    ``highest``.  ``variant``: ``{"operand_dtype": ...}`` rounds every product's
    operands, ``{"experts_operand_dtype": ...}`` the expert layers' only,
    ``{"drop_expert": j}`` leaves held expert j's term out."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import decoder_weights as dw

    ref = harness.load_module("checks", "mla_moe_decoder_reference")
    variant = variant or {}
    base = {**cfg, "operand_dtype": variant.get("operand_dtype")}
    experts_cfg = {**base, "operand_dtype": variant.get("experts_operand_dtype")
                   or base["operand_dtype"]}
    drop = variant.get("drop_expert")
    tree, eps = params["params"], cfg["rms_norm_eps"]
    dense = sorted((k for k in tree if k.startswith("dense_")), key=lambda k: int(k[6:]))
    layers = jax.tree.leaves(tree["moe"])[0].shape[0]

    def attention_half(p, x):
        return x + ref.mla(p, ref.rms_norm(x, p["attn_norm"], eps), base)

    @jax.jit
    def start(top, dense_trees, t):
        x = ref._r(t.astype(jnp.float32), base) @ ref._r(top["in_proj"].astype(jnp.float32), base)
        for d in dense_trees:
            p = dw.upcast({**d["attn"], **d["ffn"]})
            x = attention_half(p, x)
            x = x + ref.swiglu(ref.rms_norm(x, p["ffn_norm"], eps),
                               p["gate"], p["up"], p["down"], base)
        return x

    @jax.jit
    def expert_block(stack, l, x):
        p = dw.upcast(jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, l, keepdims=False), stack))
        p = {**p["attn"], **p["experts"]}
        if drop is not None:
            for name in ("experts_gate", "experts_up", "experts_down"):
                p[name] = p[name].at[drop].set(0.0)
        x = attention_half(p, x)
        y = ref.rms_norm(x, p["ffn_norm"], eps)
        out, _ = ref.expert_layer(p, y.reshape(-1, y.shape[-1]), experts_cfg)
        return x + out.reshape(x.shape)

    @jax.jit
    def finish(top, x):
        last = ref.rms_norm(x[:, -1, :], top["final_norm"], eps)
        logits = last @ top["Dense_0"]["kernel"] + top["Dense_0"]["bias"]
        value = (last @ top["Dense_1"]["kernel"] + top["Dense_1"]["bias"])[:, 0]
        return logits, value

    top = {k: tree[k] for k in ("in_proj", "final_norm", "Dense_0", "Dense_1")}
    logits, values = [], []
    with jax.default_matmul_precision("highest"):
        for at in range(0, tokens.shape[0], block):
            x = start(top, [tree[d] for d in dense], jnp.asarray(tokens[at:at + block]))
            for l in range(layers):
                x = expert_block(tree["moe"], jnp.int32(l), x)
            lg, v = finish(top, x)
            logits.append(np.asarray(lg, np.float32))
            values.append(np.asarray(v, np.float32))
    return np.concatenate(logits), np.concatenate(values)


def gaps(logits, values, ref_logits, ref_values):
    """Each decision's largest logit gap and its value gap."""
    import numpy as np

    return np.abs(logits - ref_logits).max(-1), np.abs(values - ref_values)


def distances(logits, values, actions, ref_logits, ref_values) -> dict:
    import numpy as np

    rows = np.arange(len(actions))
    logit_gap, value_gap = gaps(logits, values, ref_logits, ref_values)
    return {
        "logit_median_abs_diff": float(np.median(logit_gap)),
        "value_median_abs_diff": float(np.median(value_gap)),
        "logit_p90_abs_diff": float(np.percentile(logit_gap, 90)),
        "value_p90_abs_diff": float(np.percentile(value_gap, 90)),
        "logit_max_abs_diff": float(logit_gap.max()),
        "logit_rel_l2": float(np.linalg.norm(logits - ref_logits)
                              / max(np.linalg.norm(ref_logits), 1e-30)),
        "value_max_abs_diff": float(value_gap.max()),
        "value_rel_l2": float(np.linalg.norm(values - ref_values)
                              / max(np.linalg.norm(ref_values), 1e-30)),
        "action_gap": float((ref_logits.max(-1) - ref_logits[rows, actions]).max()),
    }


def reference_tokens(spec: dict, tape: dict, bars):
    """The benchmark's own tokens of the windows ending at ``bars``
    (``checks/window_tokens.py``); ``tape``: ``values`` (the tape's feature
    columns) and ``window``."""
    wt = harness.load_module("checks", "window_tokens")
    enc = spec["tokens"]
    return wt.tokens_at(tape["values"], bars, int(tape["window"]),
                        int(enc["zscore_window"]), float(enc["clip"]))


def check(spec: dict, params, cfg: dict, tape: dict, served: dict):
    """(ok, detail).  ``served``: for the sample, the engine's ``logits`` (N,
    actions), ``value`` (N,) and ``action`` (N,), the ``windows`` (N, W,
    token_dim) the engine was sent, the ``bars`` they end at, and where known
    each one's ``batch`` size and ``row`` in it (else None)."""
    import numpy as np

    over_limits = harness.load_module("checks", "reference_policy").over_limits
    block = int(spec["block_windows"])
    tokens = reference_tokens(spec, tape, served["bars"])
    ref_logits, ref_values = forward_by_layer(params, tokens, cfg, block)
    out = {"kind": "served_decoder", "decisions": int(len(tokens)),
           "tokens_max_abs_diff": float(np.abs(served["windows"] - tokens).max()),
           "reference_value_max_abs": float(np.abs(ref_values).max()),
           "reference_logit_max_abs": float(np.abs(ref_logits).max()),
           **distances(served["logits"], served["value"], served["action"],
                       ref_logits, ref_values)}
    out["finite"] = bool(np.isfinite(served["logits"]).all()
                         and np.isfinite(served["value"]).all())
    logit_gap, value_gap = gaps(served["logits"], served["value"], ref_logits, ref_values)
    out["per_decision"] = {"logit_gap": np.round(logit_gap, 5).tolist(),
                           "value_gap": np.round(value_gap, 5).tolist(),
                           "batch": served.get("batch"), "row": served.get("row")}
    if spec.get("run_controls"):
        variants = {"float8": {"operand_dtype": spec["lower_precision"]},
                    "experts_float8": {"experts_operand_dtype": spec["lower_precision"]},
                    "dropped_expert": {"drop_expert": 0}}
        read = {}
        for name in CONTROLS:
            if name == "shifted_windows":
                moved = reference_tokens(spec, tape, np.asarray(served["bars"]) + 1)
                lg, v = forward_by_layer(params, moved, cfg, block)
                read[name] = {"tokens_max_abs_diff": float(np.abs(moved - tokens).max())}
            else:
                lg, v = forward_by_layer(params, tokens, cfg, block, variants[name])
                read[name] = {}
            read[name].update(distances(lg, v, lg.argmax(-1), ref_logits, ref_values))
            read[name]["refused_by"] = over_limits(spec["limits"], read[name])
        out["controls"] = read
    out["over_limit"] = over_limits(spec["limits"], out)
    out["limits_not_read"] = sorted(set(spec["limits"]) - set(out))
    ok = out["finite"] and not out["over_limit"] and not out["limits_not_read"]
    return ok, out
