"""Weights of a served ``mla_moe_decoder`` trunk, made by the benchmark from
``--seed`` on the device, in one jitted call, in the types they are served in.

A serving replica holds its weights in bfloat16.  The program's initialiser
makes float32 parameters (16 bytes a parameter in training, 4 here), which for
the 47 layers of one chip's share of the 8-chip expert-parallel replica would be
20 GB: more than the chip.  So the benchmark makes the tree itself, leaf for leaf
in the structure ``policy.init`` gives (``jax.eval_shape``: nothing is
allocated), and hands it to ``engine_from_config(config, params=...)``:

  * every matrix the forward casts to the compute dtype (``_Layer.weight`` and the
    input projection): N(0, 1/fan_in) truncated at two deviations (flax's
    ``variance_scaling(1, "fan_in", "normal")``), drawn in float32 a layer at a
    time and held in ``dtype``.  The program's forward casts such a matrix to
    bfloat16 before its product, so a bfloat16-held trunk computes the same bits
    as a float32-held one with those values;
  * what the program keeps in float32 stays float32: the norms (ones), the
    router (its scores are float32 at HIGHEST), the choice bias, the actor and
    critic heads (flax ``Dense`` defaults: the same truncated normal, zero bias);
  * the router's choice bias drawn N(0, 0.02) and then balanced, layer by layer,
    by the rule the program's initialiser applies (``balanced_choice_bias``: 400
    rounds, lower the bias of an expert over the even load, raise one under it)
    on the scores of a batch of windows drawn as the traffic draws them.  The
    batch goes through the layers by the benchmark's own plain reference
    (``checks/mla_moe_decoder_reference.py``) at default precision: what sets the
    bias is the benchmark's, not the program's.

With random weights a bias left as drawn sends 5-25 % of the choices to the
experts held here by seed and layer (PERF.md section 6), and the time a decision
takes would move with the seed.

``balanced_bias`` is the program's rule written out again, and a test holds
the two to the same bits (``tests/test_rehearse_glm47flash_serve.py``).  This
module stands in for what the program lacks, a dtype for its parameters and an
initialiser fed traffic-like windows; once the program has both, the runner
passes no ``params`` and ``make`` goes (PERF.md section 7 (xl)).
"""
from __future__ import annotations

import zlib

import harness

KEEP_FLOAT32 = ("router", "kernel")   # matrices the program reads in float32
BIAS = "e_score_correction_bias"


def reference_cfg(policy_kwargs: dict) -> dict:
    """The reference's configuration dict from the program's ``policy_kwargs``."""
    cfg = {"first_k_dense_replace": 1, "norm_topk_prob": True, "expert_offset": 0,
           "n_shared_experts": 1, **dict(policy_kwargs)}
    cfg["experts_held"] = cfg.get("experts_held") or cfg["n_routed_experts"]
    return cfg


def _kind(name: str) -> str:
    if name.endswith("norm"):
        return "ones"
    if name == "bias":
        return "zeros"
    if name == BIAS:
        return "choice_bias"
    return "matrix"


def _draw(name, shape, key, dtype):
    import jax
    import jax.numpy as jnp

    kind = _kind(name)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "choice_bias":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    # flax's truncated normal, unit variance after truncation at two deviations
    std = (1.0 / shape[-2]) ** 0.5 / 0.87962566103423978
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std
    return w.astype(jnp.float32 if name in KEEP_FLOAT32 else dtype)


def _tree_draw(abstract, key, dtype, lead: int = 0):
    """Every leaf of ``abstract`` drawn by its name (the leading ``lead`` axes
    left off: one layer of a stack), each from a key of its own path."""
    import jax

    def one(path, leaf):
        tag = "/".join(str(getattr(p, "key", p)) for p in path)
        name = str(getattr(path[-1], "key", path[-1]))
        return _draw(name, leaf.shape[lead:],
                     jax.random.fold_in(key, zlib.crc32(tag.encode())), dtype)

    return jax.tree_util.tree_map_with_path(one, abstract)


def balanced_bias(scores, bias, cfg: dict, rounds: int = 400):
    """The program's balancing rule (``mla_moe_decoder.balanced_choice_bias``,
    the published rule that trains the bias) on ``scores`` (T, n_routed)."""
    import jax
    import jax.numpy as jnp

    tokens, n = scores.shape
    k = int(cfg["num_experts_per_tok"])
    even = tokens * k / n

    def step(i, b):
        _, idx = jax.lax.top_k(scores + b, k)
        load = jnp.sum(idx[..., None] == jnp.arange(n, dtype=idx.dtype), axis=(0, 1))
        rate = 0.05 * 1e-3 ** (i / (rounds - 1))
        return b - rate * jnp.clip(load / even - 1.0, -1.0, 1.0)

    return jax.lax.fori_loop(0, rounds, step, bias.astype(jnp.float32))


def upcast(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def make(abstract, tokens, seed: int, cfg: dict, dtype):
    """The policy's parameter tree (``abstract``: ``jax.eval_shape`` of its
    ``init``) drawn from ``seed`` in one jitted call on the default device;
    ``tokens`` (N, W, token_dim) float32 are the windows the choice bias is
    balanced on.  The trunk must be the latent-attention one: leading dense
    layers ``dense_<i>`` and one stack ``moe`` of expert layers."""
    import jax
    import jax.numpy as jnp

    ref = harness.load_module("checks", "mla_moe_decoder_reference")
    tree = abstract["params"]
    dense = sorted((k for k in tree if k.startswith("dense_")), key=lambda k: int(k[6:]))
    if set(tree) != {"in_proj", "final_norm", "Dense_0", "Dense_1", "moe", *dense}:
        raise ValueError(f"not a latent-attention trunk with one expert stack: {sorted(tree)}")
    eps = cfg["rms_norm_eps"]
    layers = jax.tree.leaves(tree["moe"])[0].shape[0]

    def build(key, tokens):
        keys = dict(zip(("top", "dense", "moe"), jax.random.split(key, 3)))
        out = _tree_draw({name: tree[name] for name in
                          ("in_proj", "final_norm", "Dense_0", "Dense_1")}, keys["top"], dtype)
        x = tokens.astype(jnp.float32) @ out["in_proj"].astype(jnp.float32)
        for i, name in enumerate(dense):
            out[name] = _tree_draw(tree[name], jax.random.fold_in(keys["dense"], i), dtype)
            p = upcast({**out[name]["attn"], **out[name]["ffn"]})
            x = x + ref.mla(p, ref.rms_norm(x, p["attn_norm"], eps), cfg)
            x = x + ref.swiglu(ref.rms_norm(x, p["ffn_norm"], eps),
                               p["gate"], p["up"], p["down"], cfg)

        def layer(x, l):
            drawn = _tree_draw(tree["moe"], jax.random.fold_in(keys["moe"], l), dtype, lead=1)
            p = upcast({**drawn["attn"], **drawn["experts"]})
            x = x + ref.mla(p, ref.rms_norm(x, p["attn_norm"], eps), cfg)
            y = ref.rms_norm(x, p["ffn_norm"], eps).reshape(-1, x.shape[-1])
            scores = jax.nn.sigmoid(y @ p["router"])
            p[BIAS] = drawn["experts"][BIAS] = balanced_bias(scores, p[BIAS], cfg)
            moe_out, _ = ref.expert_layer(p, y, cfg)
            return x + moe_out.reshape(x.shape), drawn

        _, out["moe"] = jax.lax.scan(layer, x, jnp.arange(layers))
        return {"params": out}

    key = jax.random.PRNGKey(harness.seed31(seed, 5))
    return jax.jit(build)(key, jnp.asarray(tokens, jnp.float32))
