"""Plain reference of the ``mla_moe_decoder`` policy: forward, PPO loss,
gradients and the optimizer's update.  Written from the layer equations (ISSUE 29), not from the
module: ``jax.numpy`` only, float32, every product under
``jax.default_matmul_precision("highest")``, a dense loop over the experts
with a mask (written as ``lax.scan`` where the bodies are identical: over the
experts held, and over the expert layers), no sort, no kernel, no flax, no cache.

Equations (x: (W, hidden) per env; RMSNorm eps ``rms_norm_eps``; pre-norm
residual blocks):

  h0 = tokens . W_in                         (no position table: RoPE only)
  block l:  h = x + MLA(RMSNorm(x));  y = h + FFN_l(RMSNorm(h))
  FFN_l:    dense SwiGLU for l < first_k_dense_replace, else the expert layer
  MLA:      c_q = RMSNorm(x W_qa);  q = c_q W_qb -> heads x (nope | rope)
            [c_kv | k_r] = x W_kva;  c_kv = RMSNorm(c_kv)
            [k_nope | v] = c_kv W_kvb -> heads x (nope | v)
            RoPE(theta, position = index in the window, ADJACENT pairs) on
            q_rope and on k_r; k_r is shared by all heads; k = k_nope | k_r
            scores q.k / sqrt(nope + rope), causal softmax, concat_heads(P v) W_o
  experts:  s = sigmoid(x W_r); top-k of s + b; w = s[idx] / (sum s[idx] + 1e-20)
            * routed_scaling_factor;  out = sum_k w_k E_idx_k(x) + E_shared(x),
            E = (silu(x W_g) * x W_u) W_d;  of the sum's terms only those whose
            expert lies in [expert_offset, expert_offset + experts_held) are
            computed (the chip's share); the partial result goes on
  readout:  final RMSNorm, LAST position -> logits (3) and value (1)

Departures from the published model, each also in the configuration file:
the token embedding is this repo's feature projection ``W_in``; there is no
LM head and no multi-token-prediction layer; ``b`` is a fixed draw.

Parameters are a plain dict (``from_policy_params`` reads the module's
tree by name); ``cfg`` is a dict under the published config's key names
plus ``n_layers``, ``first_k_dense_replace``, ``experts_held``,
``expert_offset``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def from_policy_params(params, cfg):
    """The module's parameter tree as the reference's dict, float32 (arrays
    stay where they are, device or host): the leading dense layers a list of
    per-layer dicts, the expert layers ONE dict of arrays stacked over the
    layers (as the module holds them)."""
    tree = jax.tree.map(lambda a: a.astype(jnp.float32), params["params"])
    dense = min(int(cfg["first_k_dense_replace"]), int(cfg["n_layers"]))
    out = {
        "in_proj": tree["in_proj"],
        "dense": [{**tree[f"dense_{i}"]["attn"], **tree[f"dense_{i}"]["ffn"]}
                  for i in range(dense)],
        "final_norm": tree["final_norm"],
        "actor_w": tree["Dense_0"]["kernel"], "actor_b": tree["Dense_0"]["bias"],
        "critic_w": tree["Dense_1"]["kernel"], "critic_b": tree["Dense_1"]["bias"],
    }
    if int(cfg["n_layers"]) > dense:
        out["moe"] = {**tree["moe"]["attn"], **tree["moe"]["experts"]}
    return out


def _r(x, cfg):
    """``x`` as a matrix product's operand: as it is, or rounded to
    ``cfg["operand_dtype"]`` (straight through for the gradient) where the
    reference is asked what a LOWER precision would give."""
    dtype = cfg.get("operand_dtype")
    if not dtype:
        return x
    return x + jax.lax.stop_gradient(x.astype(dtype).astype(x.dtype) - x)


def rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, theta):
    """x (..., W, d): dims (2i, 2i+1) rotate by position * theta^(-2i/d)."""
    window, d = x.shape[-2], x.shape[-1]
    position = jnp.arange(window, dtype=jnp.float32)[:, None]
    angle = position * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)[None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                     odd * jnp.cos(angle) + even * jnp.sin(angle)], axis=-1)
    return out.reshape(x.shape)


def swiglu(x, gate, up, down, cfg):
    x = _r(x, cfg)
    hidden = jax.nn.silu(x @ _r(gate, cfg)) * (x @ _r(up, cfg))
    return _r(hidden, cfg) @ _r(down, cfg)


def mla(p, x, cfg):
    """x (B, W, hidden) -> (B, W, hidden)."""
    heads, nope, rot, vdim = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                              cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    eps, batch, window = cfg["rms_norm_eps"], x.shape[0], x.shape[1]
    x = _r(x, cfg)
    c_q = rms_norm(x @ _r(p["q_a"], cfg), p["q_a_norm"], eps)
    q = (_r(c_q, cfg) @ _r(p["q_b"], cfg)).reshape(batch, window, heads, nope + rot).transpose(0, 2, 1, 3)
    kv_a = x @ _r(p["kv_a"], cfg)
    c_kv = rms_norm(kv_a[..., :cfg["kv_lora_rank"]], p["kv_a_norm"], eps)
    k_r = rope(kv_a[..., cfg["kv_lora_rank"]:], cfg["rope_theta"])        # (B, W, rot)
    kv = (_r(c_kv, cfg) @ _r(p["kv_b"], cfg)).reshape(batch, window, heads, nope + vdim).transpose(0, 2, 1, 3)
    k_nope, v = _r(kv[..., :nope], cfg), _r(kv[..., nope:], cfg)
    q_nope, q_r = _r(q[..., :nope], cfg), _r(rope(q[..., nope:], cfg["rope_theta"]), cfg)
    k_r = _r(k_r, cfg)
    scores = (jnp.einsum("bhqd,bhkd->bhqk", q_nope, k_nope)
              + jnp.einsum("bhqd,bkd->bhqk", q_r, k_r)) / jnp.sqrt(float(nope + rot))
    causal = jnp.arange(window)[:, None] >= jnp.arange(window)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bhkd->bqhd", _r(probs, cfg), v).reshape(
        batch, window, heads * vdim)
    return _r(out, cfg) @ _r(p["o"], cfg)


def router(p, x, cfg):
    """x (T, hidden) -> (scores (T, routed), idx (T, k), weights (T, k))."""
    scores = jax.nn.sigmoid(x @ p["router"])
    _, idx = jax.lax.top_k(scores + p["e_score_correction_bias"], cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return scores, idx, chosen * cfg["routed_scaling_factor"]


def expert_layer(p, x, cfg, shared: bool = True):
    """x (T, hidden) -> (the share's partial sum (+ the shared expert),
    idx).  A loop over the experts held (``lax.scan`` over their stacked
    weights: one body, the same sum); each sees every token, masked."""
    _, idx, weights = router(p, x, cfg)
    idx = jax.lax.stop_gradient(idx)

    def add_expert(out, expert):
        j, gate, up, down = expert
        w_j = jnp.sum(jnp.where(idx == cfg["expert_offset"] + j, weights, 0.0), axis=-1)
        return out + w_j[:, None] * swiglu(x, gate, up, down, cfg), None

    held = cfg["experts_held"]
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (
        jnp.arange(held), p["experts_gate"][:held], p["experts_up"][:held],
        p["experts_down"][:held]))
    if shared and cfg.get("n_shared_experts", 1):
        out = out + swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"], cfg)
    return out, idx


def forward(params, tokens, cfg, with_routing: bool = False):
    """tokens (B, W, token_dim) -> logits (B, actions), value (B,) [, the
    expert choices of every expert layer, (layers, B*W, k)].  The identical
    expert layers are one ``lax.scan`` over their stacked parameters."""
    eps = cfg["rms_norm_eps"]

    def expert_block(x, p):
        x = x + mla(p, rms_norm(x, p["attn_norm"], eps), cfg)
        y = rms_norm(x, p["ffn_norm"], eps)
        out, idx = expert_layer(p, y.reshape(-1, y.shape[-1]), cfg)
        return x + out.reshape(x.shape), idx

    with jax.default_matmul_precision("highest"):
        x = _r(tokens.astype(jnp.float32), cfg) @ _r(params["in_proj"], cfg)
        for p in params["dense"]:
            x = x + mla(p, rms_norm(x, p["attn_norm"], eps), cfg)
            x = x + swiglu(rms_norm(x, p["ffn_norm"], eps), p["gate"], p["up"], p["down"], cfg)
        routing = jnp.zeros((0,), jnp.int32)
        if "moe" in params:
            x, routing = jax.lax.scan(expert_block, x, params["moe"])
        last = rms_norm(x[:, -1, :], params["final_norm"], eps)
        logits = last @ params["actor_w"] + params["actor_b"]
        value = (last @ params["critic_w"] + params["critic_b"])[:, 0]
    return (logits, value, routing) if with_routing else (logits, value)


def ppo_terms(params, batch, cfg, hyper):
    """The SUM over the batch's samples of the three parts of the PPO loss
    of ``train/ppo.py::_loss`` (discrete actions), ``batch["adv"]`` already
    normalised: (sum of -min(ratio adv, clip(ratio) adv), sum of
    0.5 (value - ret)^2, sum of the entropy of the action distribution)."""
    logits, value = forward(params, batch["obs"], cfg)
    logp_all = jax.nn.log_softmax(logits)
    logp = jnp.take_along_axis(logp_all, batch["action"][:, None], axis=1)[:, 0]
    ratio = jnp.exp(logp - batch["logp"])
    clipped = jnp.clip(ratio, 1.0 - hyper["clip_eps"], 1.0 + hyper["clip_eps"])
    policy = -jnp.sum(jnp.minimum(ratio * batch["adv"], clipped * batch["adv"]))
    value_loss = 0.5 * jnp.sum((value - batch["ret"]) ** 2)
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all)
    return policy, value_loss, entropy


def ppo_loss(params, batch, cfg, hyper):
    """``train/ppo.py::_loss`` on a batch of N samples (obs (N, W, token_dim),
    action, logp, adv, ret): advantages normalised over the batch, then
    policy + vf_coef * value - ent_coef * entropy, each a mean."""
    adv = batch["adv"]
    batch = {**batch, "adv": (adv - adv.mean()) / (adv.std() + 1e-8)}
    n = adv.shape[0]
    policy, value_loss, entropy = ppo_terms(params, batch, cfg, hyper)
    return (policy + hyper["vf_coef"] * value_loss - hyper["ent_coef"] * entropy) / n


_BLOCK_STEPS = {}


def _block_step(cfg, hyper, n):
    """``(total, params, piece) -> (piece's part of the loss, total + its
    gradient)`` for a batch of ``n`` samples, jitted once per (cfg, hyper, n)."""
    key = (tuple(sorted(cfg.items())), tuple(sorted(hyper.items())), n)
    if key not in _BLOCK_STEPS:
        def part(params, piece):
            policy, value_loss, entropy = ppo_terms(params, piece, cfg, hyper)
            return (policy + hyper["vf_coef"] * value_loss - hyper["ent_coef"] * entropy) / n

        def add_part(total, params, piece):
            loss, grads = jax.value_and_grad(part)(params, piece)
            return loss, jax.tree.map(jnp.add, total, grads)

        _BLOCK_STEPS[key] = jax.jit(add_part, donate_argnums=0)
    return _BLOCK_STEPS[key]


def ppo_loss_and_grads(params, batch, cfg, hyper, block: int = 0):
    """Loss and its gradient by ``jax.grad`` of the forward above.  Every
    part of the loss is a mean over samples once the advantages are
    normalised, so ``block`` > 0 sums the gradient over blocks of that many
    samples (the same number; a block's activations are all that is held)."""
    n = batch["adv"].shape[0]
    adv = batch["adv"]
    batch = {**batch, "adv": (adv - adv.mean()) / (adv.std() + 1e-8)}
    step = _block_step(cfg, {k: hyper[k] for k in ("clip_eps", "vf_coef", "ent_coef")}, n)
    loss, grads = 0.0, jax.tree.map(jnp.zeros_like, params)
    for at in range(0, n, block or n):
        piece = {k: v[at:at + (block or n)] for k, v in batch.items()}
        piece_loss, grads = step(grads, params, piece)
        loss = loss + piece_loss
    return loss, grads


def adam_init(params):
    """(first moments, second moments, steps taken): zeros, kept on the HOST
    (numpy): the reference then holds parameters and one gradient on the
    device, less than the program it is compared with."""
    import numpy as np

    zeros = [np.zeros(p.shape, np.float32) for p in jax.tree.leaves(params)]
    return zeros, [z.copy() for z in zeros], 0


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adam_leaf(p, g, mu, nu, scale, count, lr):
    g = g * scale
    mu = 0.9 * mu + 0.1 * g
    nu = 0.999 * nu + 0.001 * g * g
    step = (mu / (1.0 - 0.9 ** count)) / (jnp.sqrt(nu / (1.0 - 0.999 ** count)) + 1e-8)
    return p - lr * step, mu, nu


def adam_update(params, grads, moments, hyper):
    """One update of ``train/ppo.py::_make_optimizer``: the gradient scaled
    down to ``max_grad_norm`` in global L2 norm where it is over it, then Adam
    (b1 0.9, b2 0.999, eps 1e-8, both moments bias-corrected by the steps
    taken), ``p <- p - lr m_hat / (sqrt(v_hat) + eps)``; float32, leaf by
    leaf.  ``moments`` from :func:`adam_init` -> (params, moments)."""
    import numpy as np

    mu, nu, count = moments
    leaves, tree = jax.tree.flatten(params)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.where(norm < hyper["max_grad_norm"], 1.0, hyper["max_grad_norm"] / norm)
    new = []
    for i, (p, g) in enumerate(zip(leaves, jax.tree.leaves(grads))):
        p, m, v = _adam_leaf(p, g, jnp.asarray(mu[i]), jnp.asarray(nu[i]), scale,
                             jnp.float32(count + 1), jnp.float32(hyper["lr"]))
        mu[i], nu[i] = np.asarray(m), np.asarray(v)
        new.append(p)
    return jax.tree.unflatten(tree, new), (mu, nu, count + 1)
