"""A decoder trunk with latent attention and sparse experts, as a policy.

``mla_moe_decoder``: pre-norm residual blocks over the bar window, one
bar one token.  Each block is multi-head latent attention (low-rank query
and key-value paths, rotary positions on a part of each head, one rotary
key shared by all heads, causal) and a gated feed-forward: dense in the
leading ``first_k_dense_replace`` layers, an expert layer after them.  The
last position's state feeds the actor and the critic head.

A block takes its token mixer by the layer's KIND (``layer_kinds``), of four:

  ``latent_attention``  ``LatentAttention`` (scope ``attention``): the default of
                        every layer; ``q_lora_rank`` 0 is latent attention without
                        the low-rank query path, ``attn_output_gate`` its
                        head-wise sigmoid gate
  ``linear_attention``  ``KimiDeltaAttention`` (scope ``linear_attention``): a
                        layer whose per-head state is a key x value matrix under
                        the gated delta rule with a decay of its own for every key
                        channel (``ops/kda_chunk_scan.py``); with
                        ``layer_group_size`` n > 0 every layer but the n-th
                        (``(l + 1) % n == 0``, which keeps latent attention)
  ``conv``              ``ShortConv`` (scope ``short_conv``): a gated short
                        convolution, ``out = (C * conv(B * u)) W_out`` with
                        ``B, C, u`` the thirds of one projection and
                        ``conv_L_cache`` causal taps a channel (the same
                        ``causal_conv`` KDA's projections pass through): no
                        state, no scan, no score matrix
  ``full_attention``    ``GroupedQueryAttention`` (scope ``attention``):
                        ``num_key_value_heads`` key-value heads shared by runs of
                        query heads, RMSNorm over each head of q and of k, rotary
                        positions over the whole head, causal

The last two are named by the published ``layer_types`` list, one entry a layer
(``policy_kwargs.layer_types``); the first two by ``layer_group_size``.  ``n_group``
/ ``topk_group`` are the router's choice by groups of experts.

The expert layer is TOLD which experts it holds (``experts_held`` of
``n_routed_experts`` from ``expert_offset``): the router scores all
experts and keeps its top-k, the layer computes the terms of the sum
whose expert it holds, for the tokens routed there, plus the shared
expert every token passes through (none with ``n_shared_experts`` 0: no
``shared_*`` parameter, no ``moe_shared`` scope), and hands the partial result on.
Nothing stands in for the chips that hold the other experts.  Dispatch is
dropless: token choices are placed by expert in a buffer (twice the expected
rows where the batch fits that, the worst case's rows else), a grouped matrix
product runs over the tiles in use (the Mosaic kernel of
``ops/grouped_matmul.py``, interpreted on a CPU), and each token sums the rows
of its choices.

The router's choice bias takes no gradient.  It is drawn from the key and
then balanced, once, on the batch the module is initialised on
(``balanced_choice_bias``: the rule that trains the published bias), which
a trainer makes a batch like the ones it will meet (``PPOTrainer._first_batch``):
a trained router's bias carries the balance of the experts' loads, and random
weights have none.

Parameters are float32, compute is ``dtype``; RMSNorm, rotary angles,
the router's scores and the softmax of attention run in float32.  The
module takes any leading batch dims itself (``takes_batch``): the expert
layer sorts the tokens of the WHOLE batch, so a trainer calls it on the
batch instead of vmapping it over envs.  The expert layers of a
configuration are one ``nn.scan`` over stacked parameters for every run of one
kind (``layer_runs``), each block rematerialised in the backward pass
(``remat``).  Counters (``COUNTERS``): the expert layers' ``moe_held_share``,
``moe_load_max_over_mean`` and ``moe_short_buffer_share`` (the share of layer
passes whose choices fit the short buffer); ``kda_log_decay_mean`` where a layer is linear
attention; ``short_conv_gate_rms`` where one is a convolution (the root mean
square of ``B * u`` ahead of the taps, the mean over those layers: a gate that
has died reads 0, one that has blown up reads it).

The plain reference of the same equations is
``gymfx_tpu/reference/mla_moe_decoder.py``; the trunk by kinds (all four) is
``gymfx_tpu/reference/hybrid_decoder.py``.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from gymfx_tpu.telemetry import scopes

class Dims(NamedTuple):
    """The widths of the block, under the names of the published config."""

    hidden_size: int = 2048
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    num_attention_heads: int = 20
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    experts_held: int = 64
    expert_offset: int = 0
    n_group: int = 1
    topk_group: int = 1
    attn_output_gate: bool = False
    layer_group_size: int = 0
    kda_head_dim: int = 128
    kda_conv_size: int = 4
    kda_lower_bound: float = -5.0
    kda_chunk: int = 64
    num_key_value_heads: int = 8
    conv_L_cache: int = 3


# the kinds of token mixer (``layer_kinds``); the last two are the published
# ``layer_types`` entries
LATENT, LINEAR, CONV, FULL = "latent_attention", "linear_attention", "conv", "full_attention"


_matrix = nn.initializers.variance_scaling(
    1.0, "fan_in", "normal", in_axis=-2, out_axis=-1)
_expert_matrix = nn.initializers.variance_scaling(
    1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,))


def rms_norm(x, weight, eps):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale * weight).astype(x.dtype)


def rope_interleaved(x, theta):
    """Rotary positions on the last dim of ``x`` (..., W, heads, d), the
    position the index in the window; pairs are ADJACENT dims (2i, 2i+1)."""
    window, d = x.shape[-3], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(window, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    return (nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def choose(choice, dims: Dims):
    """The (T, k) experts with the largest ``choice`` scores (T, n_routed).
    With ``n_group`` > 1 the experts are ``n_group`` runs of neighbours: a
    group's score is the sum of its two largest choice scores, the
    ``topk_group`` best groups stay, and the top-k is taken inside them."""
    if dims.n_group > 1 and dims.topk_group < dims.n_group:
        grouped = choice.reshape(choice.shape[0], dims.n_group, -1)
        best_two, _ = jax.lax.top_k(grouped, 2)
        _, kept = jax.lax.top_k(jnp.sum(best_two, axis=-1), dims.topk_group)
        stays = jnp.any(kept[:, :, None] == jnp.arange(dims.n_group)[None, None, :], axis=1)
        choice = jnp.where(stays[:, :, None], grouped, -jnp.inf).reshape(choice.shape)
    return jax.lax.top_k(choice, dims.num_experts_per_tok)[1]


def route(scores, bias, dims: Dims):
    """Top-k of ``scores + bias`` (``choose``); the weights are the
    chosen SCORES (the bias steers the choice only), renormalised and
    scaled.  ``scores`` (T, n_routed) float32 -> (idx, weights) (T, k)."""
    idx = choose(scores + jax.lax.stop_gradient(bias), dims)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if dims.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return idx, weights * dims.routed_scaling_factor


def balanced_choice_bias(scores, bias, dims: Dims):
    """The choice bias a trained router carries, for weights that were not
    trained: from ``bias``, the published balancing rule (lower the bias of
    an expert over the even load, raise one under it) on the ``scores``
    (T, n_routed) of the batch the layer is initialised on, 400 rounds with a
    step that shrinks from 0.05 to 5e-5.  It moves the choice only; where the tokens
    are all alike (a window of padding) no bias can part them and it settles
    within the steps' sum of the start."""
    tokens, n = scores.shape
    even = tokens * dims.num_experts_per_tok / n
    scores = jax.lax.stop_gradient(scores)
    rounds = 400

    def step(i, b):
        idx = choose(scores + b, dims)
        load = jnp.sum(idx[..., None] == jnp.arange(n, dtype=idx.dtype), axis=(0, 1))
        rate = 0.05 * 1e-3 ** (i / (rounds - 1))
        return b - rate * jnp.clip(load / even - 1.0, -1.0, 1.0)

    return jax.lax.fori_loop(0, rounds, step, bias.astype(jnp.float32))


class Plan(NamedTuple):
    """Where each token choice goes in the sorted buffer, from both ends.
    Choices are numbered choice-major, ``c = j * T + t`` for token t's j-th
    choice: a (k, T) array is then k runs of T, and nothing is re-laid-out."""

    token: Any        # (rows,) the token whose choice sits in row r (0 where padding)
    choice: Any       # (rows,) that choice's number c; k * T where the row is padding
    valid: Any        # (rows,) bool
    dest: Any         # (k, T) the row of choice (j, t); rows where its expert is not held
    held: Any         # (k, T) bool
    group_sizes: Any  # (experts_held,) rows of each expert, whole tiles
    loads: Any        # (experts_held,) token choices of each expert


def buffer_rows(tokens: int, dims: Dims, align: int, worst: bool = True) -> int:
    """Rows of the sorted buffer, in whole tiles of ``align`` rows.  ``worst``:
    every choice of every token may fall on an expert held here (dropless).
    Else TWICE what the experts held here expect of ``tokens`` tokens, and no
    less than an eighth of the worst case: a share of under one expert in 16
    expects so few rows that ONE expert running hot fills twice of them (8 of
    512 held, PR 33: the held experts alone get a gradient, a few steps make
    one of them hot, and once it drew 3.5 % of a layer's choices every pass of
    that layer went through the worst case's buffer: the step's time moved
    1 % with the seed, and still 0.4 % with a floor of a sixteenth).  Either
    way one tile more per expert, for the padding of its last one."""
    k, held = dims.num_experts_per_tok, dims.experts_held
    rows = tokens * min(k, held)
    if not worst:
        expected = -(-tokens * k * held // dims.n_routed_experts)
        rows = min(rows, max(2 * expected, rows // 8))
    return -(-rows // align) * align + held * align


def expert_loads(idx, dims: Dims):
    """(key of each choice, choice-major: its expert's place here,
    ``experts_held`` where the expert is not held; the one-hot of the keys;
    choices of each expert held) -- the part of the plan that does not depend
    on the buffer."""
    local = idx.T.reshape(-1) - dims.expert_offset
    held = (local >= 0) & (local < dims.experts_held)
    key = jnp.where(held, local, dims.experts_held).astype(jnp.int32)
    onehot = key[:, None] == jnp.arange(dims.experts_held, dtype=jnp.int32)[None, :]
    return key, onehot, jnp.sum(onehot, axis=0, dtype=jnp.int32)


def padded_sizes(loads, align: int):
    """Every expert's rows as whole tiles, at least one."""
    return jnp.maximum(align, -(-loads // align) * align)


def fits_short_buffer(idx, dims: Dims, align: int):
    """Whether the rows the (T, k) choices take on the experts held here,
    every expert's in whole tiles, fit the short buffer (bool scalar); never
    where the short buffer would be no shorter than the worst case's."""
    short, worst = (buffer_rows(idx.shape[0], dims, align, worst=w) for w in (False, True))
    if short >= worst:
        return jnp.zeros((), bool)
    return jnp.sum(padded_sizes(expert_loads(idx, dims)[2], align)) <= short


def routing_plan(idx, dims: Dims, align: int, rows: int = 0) -> Plan:
    """Place the (T, k) expert choices in a buffer of ``rows`` rows (the
    worst case by default) sorted by expert: the experts held here in order,
    every choice behind its expert's earlier ones; the others out of the
    buffer.  Every expert's rows start at a multiple of ``align`` and every
    expert has at least one tile.  No sort: a choice's rank among its
    expert's is a running count."""
    tokens, k = idx.shape
    rows = rows or buffer_rows(tokens, dims, align)
    key, onehot, loads = expert_loads(idx, dims)
    sizes = padded_sizes(loads, align)
    rank = jnp.sum(jnp.where(onehot, jnp.cumsum(onehot, axis=0, dtype=jnp.int32) - 1, 0),
                   axis=1)
    held = key < dims.experts_held
    starts = jnp.cumsum(sizes) - sizes
    dest = jnp.where(held, starts[jnp.minimum(key, dims.experts_held - 1)] + rank, rows)
    choice = jnp.full(rows, k * tokens, jnp.int32).at[dest].set(
        jnp.arange(k * tokens, dtype=jnp.int32), mode="drop")
    valid = choice < k * tokens
    return Plan(jnp.where(valid, choice % tokens, 0), choice, valid,
                dest.reshape(k, tokens), held.reshape(k, tokens), sizes, loads)


def _rows_of(x, index, mask):
    """``x[index]`` where ``mask``, else 0: one gather of whole rows."""
    return jnp.where(mask[:, None], jnp.take(x, index, axis=0, mode="clip"), 0)


def _sum_of_choices(rows, plan: Plan, weights=None):
    """(T, hidden): over a token's k choices, the buffer row of each one held
    here (times its weight).  k gathers of T rows summed as they are read:
    no (k, T, hidden) array is written."""
    out = 0.0
    for j in range(plan.dest.shape[0]):
        part = _rows_of(rows, plan.dest[j], plan.held[j])
        out = out + (part if weights is None else part * weights[j][:, None])
    return out


@jax.custom_vjp
def dispatch_rows(y, plan: Plan):
    """Tokens (T, hidden) -> the sorted buffer (rows, hidden): row r holds the
    token whose choice was placed there.  A padding row holds token 0's and is
    NOT zeroed (a pass over the buffer for rows nothing reads: the way back
    takes valid rows only, and a padding row's gradient is zero, so it adds
    nothing to an expert's weights either).  The plan is a partial one-to-one
    map of choices and rows known from both ends, so the backward pass is
    gathers too (no scatter-add)."""
    return jnp.take(y, plan.token, axis=0, mode="clip")


def _dispatch_fwd(y, plan):
    return dispatch_rows(y, plan), plan


def _dispatch_bwd(plan, g):
    return _sum_of_choices(g, plan).astype(g.dtype), None


dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine_rows(ys, weights, plan: Plan):
    """The sorted buffer's outputs (rows, hidden) and the (k, T) weights ->
    (T, hidden): each token's weighted sum over its choices held here."""
    return _sum_of_choices(ys, plan, weights.astype(ys.dtype)).astype(ys.dtype)


def _combine_fwd(ys, weights, plan):
    return combine_rows(ys, weights, plan), (ys, weights, plan)


def _combine_bwd(res, g):
    ys, weights, plan = res
    # zero where the row is padding: that row's gradient is then zero with no pass of its own
    row_weight = jnp.where(plan.valid, jnp.take(weights.reshape(-1), plan.choice, mode="clip"), 0)
    g_rows = jnp.take(g, plan.token, axis=0, mode="clip")
    # a weight's gradient is the float32 dot of its row with the row's gradient, placed
    # at the row's choice (a padding row's is dropped): no row of ``ys`` is gathered again
    g_row_weight = jnp.sum(ys.astype(jnp.float32) * g_rows.astype(jnp.float32), axis=-1)
    g_weights = jnp.zeros(weights.size, jnp.float32).at[plan.choice].set(
        g_row_weight, mode="drop").reshape(weights.shape)
    return (g_rows * row_weight.astype(g.dtype)[:, None], g_weights.astype(weights.dtype),
            None)


combine_rows.defvjp(_combine_fwd, _combine_bwd)


def experts_ffn(xs, plan: Plan, w_gate, w_up, w_down, align: int):
    """SwiGLU of every row of the sorted buffer through its own expert; the
    tiles behind the last expert's are skipped."""
    from gymfx_tpu.ops.grouped_matmul import grouped_matmul, tile_groups

    tiles = tile_groups(plan.group_sizes, xs.shape[0], align)
    width = w_gate.shape[-1]
    both = grouped_matmul(xs, jnp.concatenate([w_gate, w_up], axis=-1), *tiles,
                          tile_rows=align)
    hidden = nn.silu(both[:, :width]) * both[:, width:]
    return grouped_matmul(hidden, w_down, *tiles, tile_rows=align)


def routed_experts(dims: Dims, tokens: int, align: int):
    """``(y, idx, weights, w_gate, w_up, w_down) -> (T, hidden)``: the weighted
    sum, over each token's choices, of the experts held here.  Dropless: the
    rows are moved through the short buffer where the choices on the experts
    held here fit it, through the worst case's else (``lax.cond``; what a
    buffer costs is its gathers, a row each way whether used or not).
    Forward and backward each pick their branch and keep what they compute
    inside it (a ``custom_vjp`` whose residuals are its operands):
    differentiated as it stands, a ``cond`` hands the backward pass BOTH
    branches' buffers, the one not taken filled with zeros."""
    def through(rows):
        def run(y, idx, weights, w_gate, w_up, w_down):
            with jax.named_scope(scopes.MOE_DISPATCH):
                plan = routing_plan(idx, dims, align, rows)
                xs = dispatch_rows(y, plan)
            with jax.named_scope(scopes.MOE_EXPERTS):
                ys = experts_ffn(xs, plan, w_gate, w_up, w_down, align)
            with jax.named_scope(scopes.MOE_DISPATCH):
                return combine_rows(ys, weights.T, plan)
        return run

    short, worst = (buffer_rows(tokens, dims, align, worst=w) for w in (False, True))
    if short >= worst:
        return through(worst)

    def fits(idx):
        with jax.named_scope(scopes.MOE_DISPATCH):
            return fits_short_buffer(idx, dims, align)

    def backward(rows):
        def run(g, y, idx, weights, *w):
            _, pull = jax.vjp(
                lambda y, weights, *w: through(rows)(y, idx, weights, *w),
                y, weights, *w)
            return pull(g)
        return run

    @jax.custom_vjp
    def routed(y, idx, weights, w_gate, w_up, w_down):
        return jax.lax.cond(fits(idx), through(short), through(worst),
                            y, idx, weights, w_gate, w_up, w_down)

    def routed_fwd(*operands):
        return routed(*operands), operands

    def routed_bwd(operands, g):
        gy, gweights, *gw = jax.lax.cond(
            fits(operands[1]), backward(short), backward(worst), g, *operands)
        return (gy, None, gweights, *gw)

    routed.defvjp(routed_fwd, routed_bwd)
    return routed


def tile_rows_for(tokens: int, dims: Dims) -> int:
    """Rows of a tile of the grouped product: near what one expert held here
    expects, between 128 and 512."""
    expected = tokens * dims.num_experts_per_tok // dims.n_routed_experts
    return 512 if expected >= 512 else (256 if expected >= 256 else 128)


class _Layer(nn.Module):
    """What the three parts of a block share: float32 parameters handed out
    in the compute dtype, and RMSNorm with a learned weight."""

    dims: Dims
    dtype: Any

    def weight(self, name, shape, init=_matrix):
        return self.param(name, init, shape, jnp.float32).astype(self.dtype)

    def norm(self, name, x):
        weight = self.param(name, nn.initializers.ones, (x.shape[-1],), jnp.float32)
        return rms_norm(x, weight, self.dims.rms_norm_eps)


class LatentAttention(_Layer):
    """Pre-norm multi-head latent attention: (B, W, hidden) -> the same."""

    @nn.compact
    def __call__(self, x):
        from gymfx_tpu.train.policies import dense_window_attention

        d = self.dims
        heads, nope, rope, vdim = (d.num_attention_heads, d.qk_nope_head_dim,
                                   d.qk_rope_head_dim, d.v_head_dim)
        y = self.norm("attn_norm", x)
        if d.q_lora_rank:
            c_q = self.norm("q_a_norm", y @ self.weight("q_a", (d.hidden_size, d.q_lora_rank)))
            q = c_q @ self.weight("q_b", (d.q_lora_rank, heads * (nope + rope)))
        else:
            q = y @ self.weight("q", (d.hidden_size, heads * (nope + rope)))
        q = q.reshape(*q.shape[:-1], heads, nope + rope)
        kv_a = y @ self.weight("kv_a", (d.hidden_size, d.kv_lora_rank + rope))
        c_kv = self.norm("kv_a_norm", kv_a[..., :d.kv_lora_rank])
        # the attention core (telemetry/scopes.py): from the products' outputs
        # to the output product's input, in two pieces so that no op moves
        with jax.named_scope(scopes.ATTENTION_CORE):
            k_rope = rope_interleaved(kv_a[..., None, d.kv_lora_rank:], d.rope_theta)
        kv = c_kv @ self.weight("kv_b", (d.kv_lora_rank, heads * (nope + vdim)))
        kv = kv.reshape(*kv.shape[:-1], heads, nope + vdim)
        with jax.named_scope(scopes.ATTENTION_CORE):
            q = jnp.concatenate(
                [q[..., :nope], rope_interleaved(q[..., nope:], d.rope_theta)], axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_rope, (*kv.shape[:-1], rope))], axis=-1)
            v = kv[..., nope:]
            if vdim < nope + rope:
                # the attention kernel takes ONE head width: values narrower than the
                # keys go in padded with zero columns, which come out as zeros
                v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, nope + rope - vdim)])
            a = dense_window_attention(q, k, v, causal=True)[..., :vdim]
        if d.attn_output_gate:
            gate = jax.nn.sigmoid(y @ self.weight("head_gate", (d.hidden_size, heads)))
            a = a * gate[..., None]
        return a.reshape(*a.shape[:-2], heads * vdim) @ self.weight(
            "o", (heads * vdim, d.hidden_size))


def _decay_bias(lower_bound: float):
    """Initialiser of a KDA gate's ``dt_bias``: with ``A_log`` 0 and ``W_f``'s
    part at zero a channel's log-decay is ``-1 / tau`` a step, ``tau``
    log-uniform between 1 and 1,000 positions (``lower_bound * sigmoid(bias) =
    -1 / tau``; a bound nearer 0 than -1 caps the share of it at 0.99)."""
    def init(key, shape, dtype=jnp.float32):
        tau = jnp.exp(jax.random.uniform(key, shape, dtype, 0.0, jnp.log(1000.0)))
        share = jnp.minimum(1.0 / (-lower_bound * tau), 0.99)
        return jnp.log(share) - jnp.log1p(-share)
    return init


class KimiDeltaAttention(_Layer):
    """Pre-norm Kimi Delta Attention: (B, W, hidden) -> (the same, the mean
    log-decay of the layer, float32).  q, k, v through a short causal
    convolution and SiLU; q and k of unit length a head; a log-decay for every
    key channel, bounded below; the gated delta rule over the window
    (``ops/kda_chunk_scan.py``); each head's output RMS-normalised, gated, and
    projected back."""

    @nn.compact
    def __call__(self, x):
        from gymfx_tpu.ops.kda_chunk_scan import (
            MAX_LOG_DECAY_STEP,
            causal_conv,
            kda_chunk_scan,
        )

        d = self.dims
        heads, width = d.num_attention_heads, d.kda_head_dim
        if not 0.0 <= -d.kda_lower_bound <= MAX_LOG_DECAY_STEP:
            raise ValueError(f"kda_lower_bound {d.kda_lower_bound} outside "
                             f"[-{MAX_LOG_DECAY_STEP:.2f}, 0]")
        y = self.norm("attn_norm", x)
        inner = heads * width

        def mixed(name):
            taps = self.weight(f"{name}_conv", (d.kda_conv_size, inner))
            out = nn.silu(causal_conv(y @ self.weight(name, (d.hidden_size, inner)), taps))
            return out.reshape(*out.shape[:-1], heads, width)

        def unit(t):
            t32 = t.astype(jnp.float32)
            return t32 * jax.lax.rsqrt(jnp.sum(t32 * t32, axis=-1, keepdims=True) + 1e-6)

        q = (unit(mixed("q")) * width ** -0.5).astype(self.dtype)
        k = unit(mixed("k")).astype(self.dtype)
        v = mixed("v")
        rate = jnp.exp(self.param("A_log", nn.initializers.zeros, (heads,), jnp.float32))
        dt_bias = self.param(
            "dt_bias", _decay_bias(d.kda_lower_bound), (inner,), jnp.float32)
        f = (y @ self.weight("f", (d.hidden_size, inner))).astype(jnp.float32) + dt_bias
        g = d.kda_lower_bound * jax.nn.sigmoid(
            f.reshape(*f.shape[:-1], heads, width) * rate[:, None])
        beta = jax.nn.sigmoid(
            (y @ self.weight("b", (d.hidden_size, heads))).astype(jnp.float32))
        o = kda_chunk_scan(q, k, v, g, beta, chunk=d.kda_chunk)
        weight = self.param("o_norm", nn.initializers.ones, (width,), jnp.float32)
        o = rms_norm(o, weight, d.rms_norm_eps).reshape(*o.shape[:-2], inner)
        o = o * jax.nn.sigmoid(y @ self.weight("g", (d.hidden_size, inner)))
        return o @ self.weight("o", (inner, d.hidden_size)), jnp.mean(g)


class ShortConv(_Layer):
    """Pre-norm gated short convolution: (B, W, hidden) -> (the same, the root
    mean square of the gated input ahead of the taps, float32).  One projection
    to three times the width, parted ``B | C | u``; ``conv_L_cache`` causal taps
    a channel over ``B * u`` (zeros before the window, no activation), gated by
    ``C``, projected back."""

    @nn.compact
    def __call__(self, x):
        from gymfx_tpu.ops.kda_chunk_scan import causal_conv

        d = self.dims
        y = self.norm("attn_norm", x)
        b, c, u = jnp.split(
            y @ self.weight("in_proj", (d.hidden_size, 3 * d.hidden_size)), 3, axis=-1)
        gated = b * u
        z = causal_conv(gated, self.weight("taps", (d.conv_L_cache, d.hidden_size)))
        rms = jnp.sqrt(jnp.mean(jnp.square(gated.astype(jnp.float32))))
        return (c * z) @ self.weight("out_proj", (d.hidden_size, d.hidden_size)), rms


class GroupedQueryAttention(_Layer):
    """Pre-norm grouped-query attention: (B, W, hidden) -> the same.  Query head
    h reads key-value head ``h // (heads / num_key_value_heads)``; q and k are
    RMS-normalised a head (64 weights each) and rotated over the whole head."""

    @nn.compact
    def __call__(self, x):
        from gymfx_tpu.train.policies import dense_window_attention

        d = self.dims
        heads, kv_heads = d.num_attention_heads, d.num_key_value_heads
        width = d.hidden_size // heads
        y = self.norm("attn_norm", x)

        def parted(name, n):
            out = y @ self.weight(name, (d.hidden_size, n * width))
            return out.reshape(*out.shape[:-1], n, width)

        # the attention core (telemetry/scopes.py): head norms, rotation, the
        # repeat and the call, between the products
        q = parted("q", heads)
        with jax.named_scope(scopes.ATTENTION_CORE):
            q = rope_interleaved(self.norm("q_norm", q), d.rope_theta)
        k = parted("k", kv_heads)
        with jax.named_scope(scopes.ATTENTION_CORE):
            k = rope_interleaved(self.norm("k_norm", k), d.rope_theta)
        v = parted("v", kv_heads)
        with jax.named_scope(scopes.ATTENTION_CORE):
            # the attention kernel takes ONE head count: k and v repeated to the query heads
            k, v = (jnp.repeat(t, heads // kv_heads, axis=-2) for t in (k, v))
            a = dense_window_attention(q, k, v, causal=True)
        return a.reshape(*a.shape[:-2], heads * width) @ self.weight(
            "o", (heads * width, d.hidden_size))


class DenseFfn(_Layer):
    """Pre-norm gated (SwiGLU) feed-forward of a leading dense layer."""

    @nn.compact
    def __call__(self, x):
        d = self.dims
        return swiglu(self.norm("ffn_norm", x),
                      self.weight("gate", (d.hidden_size, d.intermediate_size)),
                      self.weight("up", (d.hidden_size, d.intermediate_size)),
                      self.weight("down", (d.intermediate_size, d.hidden_size)))


class ExpertLayer(_Layer):
    """Pre-norm expert layer of the chip's share: (T, hidden) -> the partial
    sum of the experts held here plus the shared expert (where the model has
    one), the counters
    (choices on the experts held, the largest expert's load, 1 where the batch
    went through the short buffer; float32) and the (T, k) expert choices."""

    @nn.compact
    def __call__(self, x):
        d = self.dims
        tokens, k = x.shape[0], d.num_experts_per_tok
        with jax.named_scope(scopes.MOE_ROUTER):
            y = self.norm("ffn_norm", x)
            w_router = self.param(
                "router", _matrix, (d.hidden_size, d.n_routed_experts), jnp.float32)
            scores = jax.nn.sigmoid(jnp.dot(
                y.astype(jnp.float32), w_router, precision=jax.lax.Precision.HIGHEST))
            # drawn from the seed, then balanced on the batch of the init call
            bias = self.param(
                "e_score_correction_bias",
                lambda key: balanced_choice_bias(
                    scores, 0.02 * jax.random.normal(key, (d.n_routed_experts,)), d))
            idx, weights = route(scores, bias, d)
        align = tile_rows_for(tokens, d)
        shape = (d.experts_held, d.hidden_size, d.moe_intermediate_size)
        w_gate = self.weight("experts_gate", shape, _expert_matrix)
        w_up = self.weight("experts_up", shape, _expert_matrix)
        w_down = self.weight("experts_down", (shape[0], shape[2], shape[1]), _expert_matrix)

        out = routed_experts(d, tokens, align)(y, idx, weights, w_gate, w_up, w_down)
        shared = None
        if d.n_shared_experts:
            with jax.named_scope(scopes.MOE_SHARED):
                width = d.moe_intermediate_size * d.n_shared_experts
                shared = swiglu(y, self.weight("shared_gate", (d.hidden_size, width)),
                                self.weight("shared_up", (d.hidden_size, width)),
                                self.weight("shared_down", (width, d.hidden_size)))
        loads = expert_loads(idx, d)[2].astype(jnp.float32)
        # (summed behind the loads: where the accepted configurations' step has it)
        out = out if shared is None else out + shared
        short = fits_short_buffer(idx, d, align).astype(jnp.float32)
        return out, jnp.stack([jnp.sum(loads), jnp.max(loads), short]), idx


# kind -> (the mixer, its name in the parameter tree, its scope, the counter of its
# own that it returns beside its output: the policy hands out the mean over its layers)
MIXERS = {
    LATENT: (LatentAttention, "attn", scopes.ATTENTION, None),
    LINEAR: (KimiDeltaAttention, "kda", scopes.LINEAR_ATTENTION, "kda_log_decay_mean"),
    CONV: (ShortConv, "conv", scopes.SHORT_CONV, "short_conv_gate_rms"),
    FULL: (GroupedQueryAttention, "self_attn", scopes.ATTENTION, None),
}


class _Block(nn.Module):
    """One pre-norm residual block: the token mixer of the layer's ``kind``
    (``MIXERS``), then a gated feed-forward that is dense (``sparse=False``) or
    the expert layer.  Called on (B, W, hidden); returns it with (the expert
    layer's counters, its choices, the mixer's own counter: a linear-attention
    layer's mean log-decay, a convolution's gate RMS), ``None`` what the block
    has not, in the shape ``nn.scan`` wants."""

    dims: Dims
    sparse: bool
    dtype: Any
    kind: str = LATENT

    @nn.compact
    def __call__(self, x, _=None):
        mixer, name, scope, counts = MIXERS[self.kind]
        with jax.named_scope(scope):
            mixed = mixer(self.dims, self.dtype, name=name)(x)
            mixed, counter = mixed if counts else (mixed, None)
            x = x + mixed
        if not self.sparse:
            with jax.named_scope(scopes.FFN):
                x = x + DenseFfn(self.dims, self.dtype, name="ffn")(x)
            return x, (None, None, counter)
        out, counters, idx = ExpertLayer(self.dims, self.dtype, name="experts")(
            x.reshape(-1, self.dims.hidden_size))
        return x + out.reshape(x.shape), (counters, idx, counter)


def layer_kinds(n_layers: int, layer_group_size: int = 0, layer_types=None):
    """The mixer's kind of every layer: the published ``layer_types`` where a
    configuration has the list (one entry a layer held), else latent attention
    in every ``layer_group_size``-th layer and linear attention in the others
    (every layer latent with ``layer_group_size`` 0)."""
    if layer_types is not None:
        kinds = tuple(layer_types)
        unknown = sorted(set(kinds) - set(MIXERS))
        if unknown or len(kinds) != n_layers:
            raise ValueError(f"layer_types: {len(kinds)} entries for {n_layers} layers, "
                             f"unknown kinds {unknown} (known: {sorted(MIXERS)})")
        return kinds
    return tuple(LINEAR if layer_group_size and (l + 1) % layer_group_size else LATENT
                 for l in range(n_layers))


def layer_runs(kinds, first_k_dense_replace: int):
    """The trunk's layers as (name, first layer, layers, sparse, kind): every
    leading dense layer alone (``dense_<l>``), the expert layers in runs of one
    kind of mixer, each run one ``nn.scan`` (``moe`` where there is one run,
    ``moe_<first layer>`` else)."""
    n_layers = len(kinds)
    dense = min(first_k_dense_replace, n_layers)
    runs = [(f"dense_{l}", l, 1, False, kinds[l]) for l in range(dense)]
    starts = [l for l in range(dense, n_layers) if l == dense or kinds[l] != kinds[l - 1]]
    for start, end in zip(starts, starts[1:] + [n_layers]):
        name = "moe" if len(starts) == 1 else f"moe_{start}"
        runs.append((name, start, end - start, True, kinds[start]))
    return runs


class MlaMoeDecoderPolicy(nn.Module):
    """Actor-critic over the decoder trunk; tokens (..., W, token_dim)."""

    n_actions: int = 3
    dtype: Any = jnp.float32
    n_layers: int = 5
    first_k_dense_replace: int = 1
    remat: bool = True
    hidden_size: int = Dims._field_defaults["hidden_size"]
    q_lora_rank: Optional[int] = Dims._field_defaults["q_lora_rank"]   # None or 0: no such path
    kv_lora_rank: int = Dims._field_defaults["kv_lora_rank"]
    num_attention_heads: int = Dims._field_defaults["num_attention_heads"]
    qk_nope_head_dim: int = Dims._field_defaults["qk_nope_head_dim"]
    qk_rope_head_dim: int = Dims._field_defaults["qk_rope_head_dim"]
    v_head_dim: int = Dims._field_defaults["v_head_dim"]
    intermediate_size: int = Dims._field_defaults["intermediate_size"]
    moe_intermediate_size: int = Dims._field_defaults["moe_intermediate_size"]
    n_routed_experts: int = Dims._field_defaults["n_routed_experts"]
    num_experts_per_tok: int = Dims._field_defaults["num_experts_per_tok"]
    n_shared_experts: int = Dims._field_defaults["n_shared_experts"]
    routed_scaling_factor: float = Dims._field_defaults["routed_scaling_factor"]
    norm_topk_prob: bool = Dims._field_defaults["norm_topk_prob"]
    rms_norm_eps: float = Dims._field_defaults["rms_norm_eps"]
    rope_theta: float = Dims._field_defaults["rope_theta"]
    experts_held: int = 0          # 0: all of n_routed_experts
    expert_offset: int = 0
    n_group: int = Dims._field_defaults["n_group"]
    topk_group: int = Dims._field_defaults["topk_group"]
    attn_output_gate: bool = Dims._field_defaults["attn_output_gate"]
    layer_group_size: int = Dims._field_defaults["layer_group_size"]
    kda_head_dim: int = Dims._field_defaults["kda_head_dim"]
    kda_conv_size: int = Dims._field_defaults["kda_conv_size"]
    kda_lower_bound: float = Dims._field_defaults["kda_lower_bound"]
    kda_chunk: int = Dims._field_defaults["kda_chunk"]
    layer_types: Optional[tuple] = None      # the published list, one kind a layer held
    num_key_value_heads: int = Dims._field_defaults["num_key_value_heads"]
    conv_L_cache: int = Dims._field_defaults["conv_L_cache"]

    # the trainers call this module on the whole env batch (no vmap), and
    # ask it for its layers' counters inside the loss
    takes_batch = True

    def __post_init__(self):
        if isinstance(self.layer_types, list):      # JSON's list: a module's fields are hashed
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        super().__post_init__()

    @property
    def COUNTERS(self):
        """Counters the loss carries out: the expert layers' three and, of a
        trunk with linear-attention or convolution layers, those layers' own."""
        kinds = {run[4] for run in self._runs()}
        return ("moe_held_share", "moe_load_max_over_mean", "moe_short_buffer_share") + tuple(
            counts for kind, (*_, counts) in MIXERS.items() if counts and kind in kinds)

    def _runs(self):
        return layer_runs(layer_kinds(self.n_layers, self.layer_group_size, self.layer_types),
                          self.first_k_dense_replace)

    def dims(self) -> Dims:
        held = self.experts_held or self.n_routed_experts
        if not 0 <= self.expert_offset <= self.n_routed_experts - held:
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset + held} held of "
                f"{self.n_routed_experts}")
        values = {name: getattr(self, name) for name in Dims._fields}
        return Dims(**{**values, "experts_held": held,
                       "q_lora_rank": self.q_lora_rank or 0})

    @nn.compact
    def __call__(self, tokens, counters: bool = False, routing: bool = False):
        """(logits, value); with ``counters`` also the expert layers' three
        counters, with ``routing`` also their (layers, tokens, k) choices."""
        dims = self.dims()
        batch = tokens.shape[:-2]
        x = tokens.reshape(-1, *tokens.shape[-2:]).astype(self.dtype)
        w_in = self.param("in_proj", _matrix, (x.shape[-1], dims.hidden_size), jnp.float32)
        x = x @ w_in.astype(self.dtype)
        block = nn.remat(_Block) if self.remat else _Block
        stats, chosen, own = [], [], {}
        for name, _first, layers, sparse, kind in self._runs():
            if sparse:
                x, (counted, idx, counter) = nn.scan(
                    block, variable_axes={"params": 0}, split_rngs={"params": True},
                    length=layers,
                )(dims, True, self.dtype, kind, name=name)(x, None)
                stats.append(counted)
                chosen.append(idx)
            else:
                x, (_, _, counter) = block(dims, False, self.dtype, kind, name=name)(x)
                counter = counter if counter is None else counter[None]
            if counter is not None:
                own.setdefault(MIXERS[kind][3], []).append(counter)
        if not stats:
            stats, chosen = jnp.zeros((1, 3), jnp.float32), None
        elif len(stats) == 1:       # one run: its own arrays, no copy
            stats, chosen = stats[0], chosen[0]
        else:
            stats, chosen = jnp.concatenate(stats), jnp.concatenate(chosen)
        weight = self.param("final_norm", nn.initializers.ones, (dims.hidden_size,),
                            jnp.float32)
        last = rms_norm(x[:, -1, :], weight, dims.rms_norm_eps)
        logits = nn.Dense(self.n_actions, dtype=jnp.float32)(last).reshape(*batch, -1)
        value = nn.Dense(1, dtype=jnp.float32)(last).reshape(batch)
        if routing:
            return logits, value, chosen
        if not counters:
            return logits, value
        choices = x.shape[0] * x.shape[1] * dims.num_experts_per_tok
        held, largest = jnp.mean(stats[:, 0]), jnp.mean(stats[:, 1])
        counted = {
            "moe_held_share": held / choices,
            "moe_load_max_over_mean": largest * dims.experts_held / jnp.maximum(held, 1.0),
            "moe_short_buffer_share": jnp.mean(stats[:, 2]),
        }
        for name, read in own.items():
            counted[name] = jnp.mean(jnp.concatenate(read))
        return logits, value, counted

    def initial_carry(self, batch_shape=()):
        return ()

    def apply_seq(self, params, tokens, carry):
        logits, value = self.apply(params, tokens)
        return logits, value, carry
