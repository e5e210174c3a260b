"""The benchmark's OWN copy of the plain reference of the ``mla_moe_decoder``
trunk whose blocks take their mixer by kind (``gymfx_tpu/reference/hybrid_decoder.py``
at PR 35: gated short convolutions and grouped-query attention beside the
linear-attention hybrid of PR 33): the yardstick may not move with the program.
``checks/reference_policy_conv_hybrid.py`` holds the measured program against it.
Everything below the next line is that file's text.

Plain reference of the ``mla_moe_decoder`` trunk whose blocks take their
token mixer BY KIND: linear-attention layers with one latent-attention layer a
period (experts chosen by groups), or gated short convolutions with one
grouped-query attention layer a period (no shared expert): forward, PPO loss,
gradients and the optimizer's update.  Written from the layer equations
(ISSUE 33, ISSUE 35), not from the module: ``jax.numpy`` only, float32, every
product under ``jax.default_matmul_precision("highest")``, the linear
attention's recurrence POSITION BY POSITION (no chunk), the convolution as a
sum of shifted copies, attention as a masked softmax (each query head reading
its key-value head by index), a dense loop over the experts held with a mask,
no sort of tokens, no kernel, no flax, no cache.

Equations (x: (W, hidden) per window; RMSNorm eps ``rms_norm_eps``; pre-norm
residual blocks):

  h0 = tokens . W_in
  block l:  h = x + Attn_l(RMSNorm(x));  y = h + FFN_l(RMSNorm(h))
  Attn_l:   by ``layer_types[l]`` where the configuration has the list: ``conv``
            the gated short convolution, ``full_attention`` GQA; else MLA where
            (l + 1) % layer_group_size == 0 (every layer with no period), KDA else
  FFN_l:    dense SwiGLU for l < first_k_dense_replace, else the expert layer
  KDA:      q, k, v = x W_q, x W_k, x W_v, each through a causal depthwise
            convolution over positions (``kda_conv_size`` taps, zeros before
            the window) and SiLU; per head q <- q / |q| / sqrt(d),
            k <- k / |k|  (|.| = sqrt(sum of squares + 1e-6))
            g_t = kda_lower_bound * sigmoid(exp(A_log_h) (x_t W_f + dt_bias))
            beta_t = sigmoid(x_t W_b)                      (per head)
            S_0 = 0;  S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1}
                            + beta_t k_t v_t^T;   o_t = S_t^T q_t
            out = (RMSNorm_head(o_t) * sigmoid(x_t W_g)) W_o
  MLA:      q = x W_q -> heads x (nope | rope)  (no low-rank query path)
            [c_kv | k_r] = x W_kva;  c_kv = RMSNorm(c_kv)
            [k_nope | v] = c_kv W_kvb -> heads x (nope | v)
            RoPE(theta, position = index in the window, ADJACENT pairs) on
            q_rope and on k_r; k_r is shared by all heads; k = k_nope | k_r
            scores q.k / sqrt(nope + rope), causal softmax; each head's output
            times sigmoid(x W_hg)_head; concat_heads W_o
  conv:     [B | C | u] = x W_in (hidden -> 3 hidden, no bias);
            z_t = sum_j taps[j] (B * u)_{t - (n - 1) + j}, n = conv_L_cache taps a
            channel, zeros before the window, no activation; out = (C * z) W_out
  GQA:      q = x W_q -> heads x d; k, v = x W_k, x W_v -> num_key_value_heads x d
            (d = hidden / heads); RMSNorm over the d dims of each head
            of q and of k, each with its own d weights; RoPE(theta) over the whole
            head (ADJACENT pairs: the family's implementation, ``transformers``
            ``modeling_lfm2_moe.py``, pairs (i, i + d/2), the same map up to a
            fixed permutation of each head's columns of W_q and W_k, which seeded
            random weights do not tell apart); query head h reads key-value head
            h // (heads / num_key_value_heads); scores q.k / sqrt(d), causal
            softmax; concat_heads W_o
  experts:  s = sigmoid(x W_r); choice scores s + b; the experts are n_group
            runs of neighbours, a group's score the sum of its two largest
            choice scores, the topk_group best groups stay; top-k of s + b in
            them; w = s[idx] / (sum s[idx] + 1e-20) * routed_scaling_factor
            out = sum_k w_k E_idx_k(x) + E_shared(x), E = (silu(x W_g) * x W_u) W_d
            (no E_shared with n_shared_experts 0)
            of the sum's terms only those whose expert lies in
            [expert_offset, expert_offset + experts_held) are computed (the
            chip's share); the partial result goes on
  readout:  final RMSNorm, LAST position -> logits (3) and value (1)

What a limit of the comparison has to refuse can be laid on ``cfg``:
``operand_dtype`` (every product's operands rounded to it), ``n_shared_experts``
0, ``routed_scaling_factor`` 1, ``kda_no_decay`` (g = 0), ``kda_beta_one``,
``conv_no_mixing`` (taps (0, ..., 0, 1): nothing crosses positions),
``gqa_kv_by_modulo`` (query head h reads key-value head h % num_key_value_heads),
``gqa_no_qk_norm``.

Parameters are a plain dict of arrays (``from_policy_params`` reads the
module's tree by name): ``runs`` is a list of dicts, one for each run of
identical layers (``layer_runs``), every leaf stacked over the run's layers.
``cfg`` is a dict under the published config's key names plus ``n_layers``,
``first_k_dense_replace``, ``experts_held``, ``expert_offset``,
``layer_group_size``, ``kda_head_dim``, ``kda_conv_size``, ``kda_lower_bound``,
``layer_types``, ``num_key_value_heads``, ``conv_L_cache``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def layer_runs(cfg):
    """[(first layer, layers, sparse, kind)]: each leading dense layer alone,
    the expert layers in runs of one kind of mixer (``latent_attention``,
    ``linear_attention``, ``conv``, ``full_attention``)."""
    n, period = int(cfg["n_layers"]), int(cfg.get("layer_group_size") or 0)
    dense = min(int(cfg.get("first_k_dense_replace", 1)), n)
    kinds = cfg.get("layer_types") or [
        "linear_attention" if period and (layer + 1) % period else "latent_attention"
        for layer in range(n)]
    runs = [(layer, 1, False, kinds[layer]) for layer in range(dense)]
    layer = dense
    while layer < n:
        end = layer + 1
        while end < n and kinds[end] == kinds[layer]:
            end += 1
        runs.append((layer, end - layer, True, kinds[layer]))
        layer = end
    return runs


# kind -> (the mixer's name in the module's tree, the prefix of its leaves here;
# every mixer's pre-norm is ``attn_norm``)
_MIXER_LEAVES = {"latent_attention": ("attn", ""), "linear_attention": ("kda", "kda_"),
                 "conv": ("conv", "conv_"), "full_attention": ("self_attn", "gqa_")}


def from_policy_params(params, cfg):
    """The module's parameter tree as the reference's dict, float32 (arrays
    stay where they are, device or host).  A linear-attention layer's leaves
    are named ``kda_<name>`` (its norm ``attn_norm``)."""
    tree = jax.tree.map(lambda a: a.astype(jnp.float32), params["params"])
    runs = layer_runs(cfg)
    sparse_runs = sum(1 for run in runs if run[2])
    out = {
        "in_proj": tree["in_proj"], "final_norm": tree["final_norm"],
        "actor_w": tree["Dense_0"]["kernel"], "actor_b": tree["Dense_0"]["bias"],
        "critic_w": tree["Dense_1"]["kernel"], "critic_b": tree["Dense_1"]["bias"],
        "runs": [],
    }
    for first, _layers, sparse, kind in runs:
        if sparse:
            block = tree["moe" if sparse_runs == 1 else f"moe_{first}"]
        else:   # a layer alone: give its leaves the run's leading axis
            block = jax.tree.map(lambda a: a[None], tree[f"dense_{first}"])
        name, prefix = _MIXER_LEAVES[kind]
        mixer = {(k if k == "attn_norm" else prefix + k): v for k, v in block[name].items()}
        out["runs"].append({**mixer, **block["experts" if sparse else "ffn"]})
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


def short_conv(x, taps):
    """x (B, W, C), taps (n, C): y_t = sum_j taps[j] x_{t - (n - 1) + j}, zeros
    before the window; written as a loop over the window's positions' sources."""
    n, window = taps.shape[0], x.shape[1]
    out = jnp.zeros_like(x)
    for j in range(n):
        back = n - 1 - j                     # tap j reads ``back`` positions behind
        if back < window:
            moved = jnp.concatenate(
                [jnp.zeros_like(x[:, :back]), x[:, :window - back]], axis=1)
            out = out + moved * taps[j]
    return out


SEGMENT = 32    # positions between two states the recurrence's backward keeps


def delta_rule(q, k, v, g, beta, cfg):
    """The recurrence, a position at a time: q, k, g (B, W, H, K), v (B, W, H, V),
    beta (B, W, H) -> o (B, W, H, V).  The backward pass keeps the state of
    every ``SEGMENT``-th position and walks a segment again for the others."""
    batch, window, heads, kdim = k.shape

    def position(state, x):
        q_t, k_t, v_t, g_t, b_t = x                       # (B, H, .)
        state = state * jnp.exp(g_t)[..., None]           # Diag(alpha) S
        seen = jnp.einsum("bhkv,bhk->bhv", _r(state, cfg), _r(k_t, cfg))
        u = b_t[..., None] * (v_t - seen)
        state = state + jnp.einsum("bhk,bhv->bhkv", _r(k_t, cfg), _r(u, cfg))
        return state, jnp.einsum("bhkv,bhk->bhv", _r(state, cfg), _r(q_t, cfg))

    @jax.checkpoint
    def segment(state, xs):
        return jax.lax.scan(position, state, xs)

    pad = -window % SEGMENT     # zeros behind the last position change nothing before it

    def by_segment(x):
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(-1, SEGMENT, *x.shape[1:])

    state = jnp.zeros((batch, heads, kdim, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(segment, state, tuple(map(by_segment, (q, k, v, g, beta))))
    out = out.reshape(-1, *out.shape[2:])[:window]
    return jnp.moveaxis(out, 0, 1)


def kda(p, x, cfg):
    """x (B, W, hidden), already normalised -> (B, W, hidden)."""
    heads, d = cfg["num_attention_heads"], cfg.get("kda_head_dim", 128)
    batch, window = x.shape[0], x.shape[1]
    x = _r(x, cfg)

    def mixed(name):
        out = jax.nn.silu(short_conv(x @ _r(p[f"kda_{name}"], cfg), p[f"kda_{name}_conv"]))
        return out.reshape(batch, window, heads, d)

    def unit(t):
        return t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    q, k, v = unit(mixed("q")) / jnp.sqrt(float(d)), unit(mixed("k")), mixed("v")
    f = (x @ _r(p["kda_f"], cfg) + p["kda_dt_bias"]).reshape(batch, window, heads, d)
    g = cfg.get("kda_lower_bound", -5.0) * jax.nn.sigmoid(
        jnp.exp(p["kda_A_log"])[:, None] * f)
    beta = jax.nn.sigmoid(x @ _r(p["kda_b"], cfg))
    if cfg.get("kda_no_decay"):
        g = jnp.zeros_like(g)
    if cfg.get("kda_beta_one"):
        beta = jnp.ones_like(beta)
    o = delta_rule(q, k, v, g, beta, cfg)
    o = rms_norm(o, p["kda_o_norm"], cfg["rms_norm_eps"]).reshape(batch, window, heads * d)
    o = o * jax.nn.sigmoid(x @ _r(p["kda_g"], cfg))
    return _r(o, cfg) @ _r(p["kda_o"], cfg)


def mla(p, x, cfg):
    """x (B, W, hidden), already normalised -> (B, W, hidden)."""
    heads, nope, rot, vdim = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                              cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    eps, batch, window = cfg["rms_norm_eps"], x.shape[0], x.shape[1]
    x = _r(x, cfg)
    q = (x @ _r(p["q"], cfg)).reshape(batch, window, heads, nope + rot).transpose(0, 2, 1, 3)
    kv_a = x @ _r(p["kv_a"], cfg)
    c_kv = rms_norm(kv_a[..., :cfg["kv_lora_rank"]], p["kv_a_norm"], eps)
    k_r = _r(rope(kv_a[..., cfg["kv_lora_rank"]:], cfg["rope_theta"]), cfg)    # (B, W, rot)
    kv = (_r(c_kv, cfg) @ _r(p["kv_b"], cfg)).reshape(
        batch, window, heads, nope + vdim).transpose(0, 2, 1, 3)
    k_nope, v = _r(kv[..., :nope], cfg), _r(kv[..., nope:], cfg)
    q_nope, q_r = _r(q[..., :nope], cfg), _r(rope(q[..., nope:], cfg["rope_theta"]), cfg)
    scores = (jnp.einsum("bhqd,bhkd->bhqk", q_nope, k_nope)
              + jnp.einsum("bhqd,bkd->bhqk", q_r, k_r)) / jnp.sqrt(float(nope + rot))
    causal = jnp.arange(window)[:, None] >= jnp.arange(window)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bhkd->bqhd", _r(probs, cfg), v)
    if "head_gate" in p:
        out = out * jax.nn.sigmoid(x @ _r(p["head_gate"], cfg))[..., None]
    return _r(out.reshape(batch, window, heads * vdim), cfg) @ _r(p["o"], cfg)


def gated_conv(p, x, cfg):
    """x (B, W, hidden), already normalised -> (B, W, hidden)."""
    x = _r(x, cfg)
    b, c, u = jnp.split(x @ _r(p["conv_in_proj"], cfg), 3, axis=-1)
    taps = p["conv_taps"]
    if cfg.get("conv_no_mixing"):
        taps = jnp.zeros_like(taps).at[-1].set(1.0)
    return _r(c * short_conv(b * u, taps), cfg) @ _r(p["conv_out_proj"], cfg)


def gqa(p, x, cfg):
    """x (B, W, hidden), already normalised -> (B, W, hidden)."""
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    eps, batch, window = cfg["rms_norm_eps"], x.shape[0], x.shape[1]
    x = _r(x, cfg)

    def parted(name, n):
        return (x @ _r(p[name], cfg)).reshape(batch, window, n, d).transpose(0, 2, 1, 3)

    q, k, v = parted("gqa_q", heads), parted("gqa_k", kv_heads), parted("gqa_v", kv_heads)
    if not cfg.get("gqa_no_qk_norm"):
        q, k = rms_norm(q, p["gqa_q_norm"], eps), rms_norm(k, p["gqa_k_norm"], eps)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    source = jnp.arange(heads) // (heads // kv_heads)     # query head h's key-value head
    if cfg.get("gqa_kv_by_modulo"):
        source = jnp.arange(heads) % kv_heads
    scores = jnp.einsum("bhqd,bhkd->bhqk", _r(q, cfg), _r(k[:, source], cfg)) / jnp.sqrt(float(d))
    causal = jnp.arange(window)[:, None] >= jnp.arange(window)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bhkd->bqhd", _r(probs, cfg), _r(v[:, source], cfg))
    return _r(out.reshape(batch, window, heads * d), cfg) @ _r(p["gqa_o"], cfg)


MIXERS = {"latent_attention": mla, "linear_attention": kda, "conv": gated_conv,
          "full_attention": gqa}


def choose(choice, cfg):
    """(T, n) choice scores -> the (T, k) experts chosen: the topk_group best of
    n_group groups (a group's score: its two largest choice scores summed),
    then the k largest choice scores inside them.  Equal scores: the lower
    index first (a stable sort)."""
    n_group, keep = int(cfg.get("n_group") or 1), int(cfg.get("topk_group") or 1)
    if n_group > 1 and keep < n_group:
        grouped = choice.reshape(choice.shape[0], n_group, -1)
        group_score = jnp.sum(jnp.sort(grouped, axis=-1)[..., -2:], axis=-1)
        kept = jnp.argsort(-group_score, axis=-1, stable=True)[:, :keep]
        stays = jnp.zeros(group_score.shape, bool).at[
            jnp.arange(choice.shape[0])[:, None], kept].set(True)
        choice = jnp.where(stays[:, :, None], grouped, -jnp.inf).reshape(choice.shape)
    return jnp.argsort(-choice, axis=-1, stable=True)[:, :cfg["num_experts_per_tok"]]


def router(p, x, cfg):
    """x (T, hidden) -> (scores (T, routed), idx (T, k), weights (T, k))."""
    scores = jax.nn.sigmoid(x @ p["router"])
    idx = choose(scores + p["e_score_correction_bias"], cfg)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return scores, idx, chosen * cfg["routed_scaling_factor"]


def expert_layer(p, x, cfg):
    """x (T, hidden) -> (the share's partial sum (+ the shared expert), idx).
    A loop over the experts held (``lax.scan`` over their stacked weights: one
    body, the same sum); each sees every token, masked."""
    _, idx, weights = router(p, x, cfg)
    idx = jax.lax.stop_gradient(idx)

    def add_expert(out, expert):
        j, gate, up, down = expert
        w_j = jnp.sum(jnp.where(idx == cfg.get("expert_offset", 0) + j, weights, 0.0), axis=-1)
        return out + w_j[:, None] * swiglu(x, gate, up, down, cfg), None

    held = cfg["experts_held"]
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (
        jnp.arange(held), p["experts_gate"][:held], p["experts_up"][:held],
        p["experts_down"][:held]))
    if cfg.get("n_shared_experts", 1):
        out = out + swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"], cfg)
    return out, idx


def forward(params, tokens, cfg, with_routing: bool = False):
    """tokens (B, W, token_dim) -> logits (B, actions), value (B,) [, the
    expert choices of every expert layer, (layers, B*W, k)].  A run of
    identical layers is one ``lax.scan`` over its stacked parameters, each
    layer under ``jax.checkpoint`` (a backward pass holds one layer's insides)."""
    eps = cfg["rms_norm_eps"]

    def block(sparse, kind):
        @jax.checkpoint
        def run(x, p):
            x = x + MIXERS[kind](p, rms_norm(x, p["attn_norm"], eps), cfg)
            y = rms_norm(x, p["ffn_norm"], eps)
            if not sparse:
                return x + swiglu(y, p["gate"], p["up"], p["down"], cfg), None
            out, idx = expert_layer(p, y.reshape(-1, y.shape[-1]), cfg)
            return x + out.reshape(x.shape), idx
        return run

    with jax.default_matmul_precision("highest"):
        x = _r(tokens.astype(jnp.float32), cfg) @ _r(params["in_proj"], cfg)
        routing = []
        for (_first, _layers, sparse, kind), p in zip(layer_runs(cfg), params["runs"]):
            x, idx = jax.lax.scan(block(sparse, kind), x, p)
            if sparse:
                routing.append(idx)
        last = rms_norm(x[:, -1, :], params["final_norm"], eps)
        logits = last @ params["actor_w"] + params["actor_b"]
        value = (last @ params["critic_w"] + params["critic_b"])[:, 0]
    if not with_routing:
        return logits, value
    return logits, value, (jnp.concatenate(routing) if routing
                           else jnp.zeros((0,), jnp.int32))


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
    hashable = {k: tuple(v) if isinstance(v, list) else v for k, v in cfg.items()}
    key = (tuple(sorted(hashable.items())), tuple(sorted(hyper.items())), n)
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
