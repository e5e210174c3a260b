"""Policy networks (flax.linen): MLP, LSTM, Transformer.

Model families follow BASELINE.json's config ladder: 3-layer MLP
(config 3), recurrent LSTM (config 4), Transformer (config 5).  All
are actor-critic heads over the Dict observation; observations are
flattened in a fixed key order so the same policies drive any obs
layout (price windows, feature windows, stage-B/calendar blocks).

TPU notes: matmul-heavy bodies sized for the MXU; parameters can be
sharded over a 'model' mesh axis (see train/ppo.py shardings);
compute dtype is configurable (bfloat16 on TPU, f32 reference path).
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from gymfx_tpu.telemetry import scopes


class ObsSpec(NamedTuple):
    """Static layout of a Dict observation: the sorted key order plus
    each block's shape and flat size, computed ONCE per env config.

    The obs dict's structure is fixed by EnvConfig, so re-deriving
    ``sorted(obs.keys())`` (and the per-key shapes) on every encode call
    is pure overhead — at trace time in the training hot loop, and on
    EVERY host-side request in the serving hot path (serve/engine.py).
    Both paths take the spec instead."""

    keys: Tuple[str, ...]
    shapes: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]
    total_size: int


def make_obs_spec(obs: Dict[str, Any]) -> ObsSpec:
    """Derive the static flattening spec from one example obs dict."""
    keys = tuple(sorted(obs.keys()))
    shapes = tuple(
        tuple(int(s) for s in jnp.shape(obs[k])) for k in keys
    )
    sizes = tuple(math.prod(shape) if shape else 1 for shape in shapes)
    return ObsSpec(keys, shapes, sizes, sum(sizes))


def flatten_obs(obs: Dict[str, Any], spec: Optional[ObsSpec] = None) -> Any:
    """Dict obs -> flat feature vector (sorted key order, stable).

    Pass the precomputed ``spec`` in hot paths (trainer encode, serving
    featurize) so the key sort happens once per config, not per call."""
    keys = spec.keys if spec is not None else tuple(sorted(obs.keys()))
    parts = [jnp.ravel(obs[k]).astype(jnp.float32) for k in keys]
    return jnp.concatenate(parts, axis=0)


def _takes_fused_kernel(window: int) -> bool:
    """A window inside [MIN, MAX] on a TPU is the compiled kernel or its
    error; short windows (the plain-XLA twin measured faster there),
    windows beyond the kernel's declared reach and every other backend
    are the twin's."""
    from gymfx_tpu.ops.dispatch import on_tpu
    from gymfx_tpu.ops.fused_attention import (
        MAX_FUSED_WINDOW,
        MIN_FUSED_WINDOW,
    )

    return MIN_FUSED_WINDOW <= window <= MAX_FUSED_WINDOW and on_tpu()


def dense_window_attention(q, k, v, causal: bool = False):
    """Single-device attention for the token policies, (..., W, H, D):
    the fused VMEM-resident pallas kernel on TPU for LONG windows
    (ops/fused_attention.py — zero HBM score traffic, VERDICT r4 weak
    #5), the plain-XLA twin elsewhere (``_takes_fused_kernel``)."""
    from gymfx_tpu.ops.fused_attention import fused_window_attention
    from gymfx_tpu.parallel.ring_attention import full_attention

    if _takes_fused_kernel(q.shape[-3]):
        return fused_window_attention(q, k, v, causal=causal, interpret=False)
    return full_attention(q, k, v, causal=causal)


def split_heads(x, n_heads: int):
    """(..., H * D) -> the (..., H, D) view of heads packed side by side."""
    return x.reshape(*x.shape[:-1], n_heads, x.shape[-1] // n_heads)


def packed_window_attention(q, k, v, n_heads: int):
    """``dense_window_attention`` on q/k/v as a projection to ``d_model``
    writes them, heads side by side: (..., W, H * D) in and out.  The
    kernel reads narrow heads in that very layout, so on its route no
    activation is reshaped between the projections and the call."""
    from gymfx_tpu.ops.fused_attention import fused_packed_attention
    from gymfx_tpu.parallel.ring_attention import full_attention

    if _takes_fused_kernel(q.shape[-2]):
        return fused_packed_attention(
            q, k, v, n_heads=n_heads, interpret=False)
    out = full_attention(*(split_heads(x, n_heads) for x in (q, k, v)))
    return out.reshape(q.shape)


def obs_size(obs: Dict[str, Any]) -> int:
    return int(sum(int(jnp.size(v)) for v in obs.values()))


class MLPPolicy(nn.Module):
    """3-layer MLP actor-critic (BASELINE config 3)."""

    n_actions: int = 3
    hidden: Sequence[int] = (256, 256, 256)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        for width in self.hidden:
            x = nn.Dense(width, dtype=self.dtype)(x)
            x = nn.tanh(x)
        logits = nn.Dense(self.n_actions, dtype=jnp.float32)(x)
        value = nn.Dense(1, dtype=jnp.float32)(x)
        return logits, jnp.squeeze(value, axis=-1)

    def initial_carry(self, batch_shape=()):
        return ()

    def apply_seq(self, params, x, carry):
        logits, value = self.apply(params, x)
        return logits, value, carry


class LSTMPolicy(nn.Module):
    """Recurrent actor-critic; the cell carry threads through the env
    scan (BASELINE config 4)."""

    n_actions: int = 3
    hidden: int = 256
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, carry):
        x = x.astype(self.dtype)
        x = nn.tanh(nn.Dense(self.hidden, dtype=self.dtype)(x))
        cell = nn.OptimizedLSTMCell(self.hidden, dtype=self.dtype)
        carry, x = cell(carry, x)
        logits = nn.Dense(self.n_actions, dtype=jnp.float32)(x)
        value = nn.Dense(1, dtype=jnp.float32)(x)
        return logits, jnp.squeeze(value, axis=-1), carry

    def initial_carry(self, batch_shape=()):
        # (c, h) zeros — what LSTMCell.initialize_carry returns, built
        # directly (flax modules cannot be instantiated outside a scope).
        # Two distinct buffers: aliased leaves break jit donation.
        return (
            jnp.zeros((*batch_shape, self.hidden), dtype=self.dtype),
            jnp.zeros((*batch_shape, self.hidden), dtype=self.dtype),
        )

    def apply_seq(self, params, x, carry):
        return self.apply(params, x, carry)


class TransformerPolicy(nn.Module):
    """Attention over the observation window (BASELINE config 5).

    Expects the obs dict to contain at least one (window, k) block
    ('features') or (window,) blocks ('prices'/'returns'); scalar
    blocks are broadcast as extra tokens.  Attention heads and MLP
    widths are chosen to tile the MXU (dims multiples of 128).
    """

    n_actions: int = 3
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        # tokens: (window, token_dim)
        x = nn.Dense(self.d_model, dtype=self.dtype)(tokens.astype(self.dtype))
        n = x.shape[-2]
        pos = self.param(
            "pos_embed", nn.initializers.normal(0.02), (n, self.d_model), jnp.float32
        )
        x = x + pos.astype(self.dtype)
        for _ in range(self.n_layers):
            y = nn.LayerNorm(dtype=self.dtype)(x)
            y = nn.MultiHeadDotProductAttention(
                num_heads=self.n_heads, dtype=self.dtype
            )(y, y)
            x = x + y
            y = nn.LayerNorm(dtype=self.dtype)(x)
            y = nn.Dense(self.d_model * 4, dtype=self.dtype)(y)
            y = nn.gelu(y)
            y = nn.Dense(self.d_model, dtype=self.dtype)(y)
            x = x + y
        x = nn.LayerNorm(dtype=self.dtype)(x)
        pooled = jnp.mean(x, axis=-2)
        logits = nn.Dense(self.n_actions, dtype=jnp.float32)(pooled)
        value = nn.Dense(1, dtype=jnp.float32)(pooled)
        return logits, jnp.squeeze(value, axis=-1)

    def initial_carry(self, batch_shape=()):
        return ()

    def apply_seq(self, params, tokens, carry):
        logits, value = self.apply(params, tokens)
        return logits, value, carry


class PackedHeadsDense(nn.Module):
    """``nn.DenseGeneral``'s projection into heads (kernel
    ``(d_model, H, D)``, ``contract=1``) or out of them (``(H, D,
    d_model)``, ``contract=2``), applied as ONE plain matmul on heads
    packed side by side: ``(..., d_model) -> (..., H * D)`` and back.

    The parameters are DenseGeneral's — names, shapes, initial values —
    so a checkpoint written by either loads in the other; only the small
    weights are reshaped, never an activation.  On the chip an
    ``(..., H, 32)`` activation is three quarters lane padding in HBM
    (ops/fused_attention.py ``packed_lanes``)."""

    kernel_shape: Tuple[int, ...]
    contract: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        out_shape = self.kernel_shape[self.contract:]
        flat = (math.prod(self.kernel_shape[:self.contract]),
                math.prod(out_shape))

        def kernel_init(rng, shape, dtype):
            # as DenseGeneral draws it: fans of the flattened product
            return nn.linear.default_kernel_init(rng, flat, dtype).reshape(shape)

        kernel = self.param(
            "kernel", kernel_init, self.kernel_shape, jnp.float32)
        bias = self.param(
            "bias", nn.initializers.zeros_init(), out_shape, jnp.float32)
        x, kernel, bias = nn.dtypes.promote_dtype(
            x, kernel, bias, dtype=self.dtype)
        return x @ kernel.reshape(flat) + bias.reshape(flat[1])


class RingTransformerEncoder(nn.Module):
    """Transformer trunk whose attention can run sequence-parallel ring
    attention over a 'seq' mesh axis (parallel/ring_attention.py);
    returns the pooled (..., d_model) embedding.  Shared by the
    single-pair and portfolio ring policies.

    Two modes, SAME parameter structure:
      * ``seq_axis=None`` (default): ordinary full attention over the
        whole window — how the policy initializes and trains on one
        device;
      * ``seq_axis='seq', seq_shards=P``: the instance is being applied
        INSIDE a shard_map whose token axis is sharded over that mesh
        axis; attention streams K/V blocks around the ring and the
        outputs are numerically identical (up to fp error) to the
        unsharded forward with the same params.

    Use ``seq_sharded_forward`` to run the sharded mode; the ``window``
    field must be the GLOBAL token count (positional embeddings are
    sliced per shard by ring position).
    """

    window: int = 32
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    dtype: Any = jnp.float32
    seq_axis: Optional[str] = None
    seq_shards: int = 1
    # sequence-parallel backend for the sharded mode: "ring" streams
    # K/V blocks with ppermute (memory O(S/P)); "ulysses" swaps
    # heads<->sequence with two all_to_alls (full attention locally,
    # needs n_heads % shards == 0) — parallel/ulysses.py
    sp_backend: str = "ring"

    @nn.compact
    def __call__(self, tokens):
        from gymfx_tpu.parallel.ring_attention import ring_attention_inner
        from gymfx_tpu.parallel.ulysses import ulysses_attention_inner

        if self.sp_backend not in ("ring", "ulysses"):
            raise ValueError(
                f"unknown sp_backend {self.sp_backend!r} "
                "(expected 'ring' or 'ulysses')"
            )
        head_dim = self.d_model // self.n_heads
        x = nn.Dense(self.d_model, dtype=self.dtype)(tokens.astype(self.dtype))
        pos = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (self.window, self.d_model), jnp.float32,
        )
        if self.seq_axis is not None:
            sb = self.window // self.seq_shards
            idx = jax.lax.axis_index(self.seq_axis)
            pos_local = jax.lax.dynamic_slice_in_dim(pos, idx * sb, sb, 0)
        else:
            pos_local = pos
        x = x + pos_local.astype(self.dtype)

        # the two halves of each block, by name, for a device trace
        # (telemetry/scopes.py: metadata only; a scope is no flax module, so
        # the parameters keep their names).  The projections carry the names
        # flax gave the DenseGenerals they were, four a layer: a checkpoint
        # written before PR 32 loads.
        into_heads = (self.d_model, self.n_heads, head_dim)
        for layer in range(self.n_layers):
            with jax.named_scope(scopes.ATTENTION):
                y = nn.LayerNorm(dtype=self.dtype)(x)
                q, k, v = (
                    PackedHeadsDense(
                        into_heads, contract=1, dtype=self.dtype,
                        name=f"DenseGeneral_{4 * layer + i}")(y)
                    for i in range(3)
                )
                with jax.named_scope(scopes.ATTENTION_CORE):
                    if self.seq_axis is not None:
                        sp_attention = (
                            ulysses_attention_inner
                            if self.sp_backend == "ulysses"
                            else ring_attention_inner
                        )
                        a = sp_attention(
                            *(split_heads(t, self.n_heads) for t in (q, k, v)),
                            axis=self.seq_axis, n_shards=self.seq_shards,
                        ).reshape(q.shape)
                    else:
                        a = packed_window_attention(q, k, v, self.n_heads)
                y = PackedHeadsDense(
                    into_heads[1:] + into_heads[:1], contract=2,
                    dtype=self.dtype, name=f"DenseGeneral_{4 * layer + 3}")(a)
                x = x + y
            with jax.named_scope(scopes.FFN):
                y = nn.LayerNorm(dtype=self.dtype)(x)
                y = nn.Dense(self.d_model * 4, dtype=self.dtype)(y)
                y = nn.gelu(y)
                y = nn.Dense(self.d_model, dtype=self.dtype)(y)
                x = x + y

        x = nn.LayerNorm(dtype=self.dtype)(x)
        pooled = jnp.mean(x, axis=-2)
        if self.seq_axis is not None:
            # equal block sizes: the global mean is the pmean of block
            # means, and the result is replicated across the ring
            pooled = jax.lax.pmean(pooled, self.seq_axis)
        return pooled


class RingTransformerPolicy(nn.Module):
    """Actor-critic over RingTransformerEncoder (BASELINE config 5
    long-context path).  Use ``seq_sharded_forward`` for the
    sequence-sharded mode; same parameter structure in both modes."""

    n_actions: int = 3
    window: int = 32
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    dtype: Any = jnp.float32
    seq_axis: Optional[str] = None
    seq_shards: int = 1
    sp_backend: str = "ring"

    @nn.compact
    def __call__(self, tokens):
        pooled = RingTransformerEncoder(
            window=self.window, d_model=self.d_model, n_heads=self.n_heads,
            n_layers=self.n_layers, dtype=self.dtype,
            seq_axis=self.seq_axis, seq_shards=self.seq_shards,
            sp_backend=self.sp_backend,
        )(tokens)
        logits = nn.Dense(self.n_actions, dtype=jnp.float32)(pooled)
        value = nn.Dense(1, dtype=jnp.float32)(pooled)
        return logits, jnp.squeeze(value, axis=-1)

    def initial_carry(self, batch_shape=()):
        return ()

    def apply_seq(self, params, tokens, carry):
        logits, value = self.apply(params, tokens)
        return logits, value, carry


def with_seq_sharding(policy, axis: str, shards: int):
    """Same hyperparams/param structure, sharded-attention mode — any
    module with window/seq_axis/seq_shards fields (single-pair or
    portfolio ring policy).  A free function (not a method): flax would
    treat a module constructed inside a module method as a child
    submodule."""
    if policy.window % shards != 0:
        raise ValueError(
            f"seq shard count {shards} must divide window {policy.window}"
        )
    return policy.clone(seq_axis=axis, seq_shards=shards)


def seq_sharded_forward(policy, params, tokens, mesh, axis: str = "seq"):
    """Apply a ring policy with the WINDOW sharded over
    ``mesh[axis]``: tokens (..., window, token_dim) enter with their
    token axis split across devices; attention runs as a ring; the
    pooled logits/value come back replicated.  Batch dims stay
    unsharded (shard other mesh axes outside if desired)."""
    shards = mesh.shape[axis]
    sharded = with_seq_sharding(policy, axis, shards)
    nbatch = tokens.ndim - 2
    tok_spec = jax.sharding.PartitionSpec(*([None] * nbatch), axis, None)
    out_spec = jax.sharding.PartitionSpec(*([None] * nbatch))

    def f(tok_blk):
        return sharded.apply(params, tok_blk)

    from gymfx_tpu.parallel.mesh import shard_map

    fn = shard_map(
        f, mesh=mesh, in_specs=(tok_spec,),
        out_specs=(out_spec, out_spec),
    )
    return fn(tokens)


def tokens_from_obs(obs: Dict[str, Any], window: int,
                    spec: Optional[ObsSpec] = None) -> Any:
    """Obs dict -> (window, token_dim) token sequence for the
    TransformerPolicy: window-aligned blocks become per-bar token
    features; scalar blocks broadcast along the window.  Pass the
    precomputed ``spec`` in hot paths (see :func:`flatten_obs`)."""
    keys = spec.keys if spec is not None else tuple(sorted(obs.keys()))
    cols = []
    for k in keys:
        v = obs[k]
        if v.ndim >= 1 and v.shape[0] == window:
            cols.append(v.reshape(window, -1).astype(jnp.float32))
        else:
            flat = jnp.ravel(v).astype(jnp.float32)
            cols.append(jnp.broadcast_to(flat[None, :], (window, flat.shape[0])))
    return jnp.concatenate(cols, axis=-1)


def make_obs_encoder(policy_name: str, window: int, spec: ObsSpec):
    """The one obs->policy-input encoding, shared by the trainers and
    the serving engine: token policies get the (window, token_dim)
    sequence, everything else the flat vector — both through the static
    ``spec`` (no per-call key sort)."""
    if is_token_policy(policy_name):
        return lambda obs: tokens_from_obs(obs, window, spec)
    return lambda obs: flatten_obs(obs, spec)


class ContinuousMLPPolicy(nn.Module):
    """Gaussian actor-critic for action_space_mode=continuous: emits the
    mean of a Normal over the Box(-1,1,(1,)) action (state-independent
    learned log-std); the env thresholds the sampled value into
    hold/long/short (reference app/env.py:343-355)."""

    hidden: Sequence[int] = (256, 256, 256)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        for width in self.hidden:
            x = nn.tanh(nn.Dense(width, dtype=self.dtype)(x))
        mu = nn.tanh(nn.Dense(1, dtype=jnp.float32)(x))
        # explicit f32: a default-dtype param turns f64 under x64 test
        # configs and promotes actions/log-probs downstream
        log_std = self.param(
            "log_std", nn.initializers.constant(-0.5), (1,), jnp.float32
        )
        value = nn.Dense(1, dtype=jnp.float32)(x)
        return (jnp.squeeze(mu, -1), jnp.broadcast_to(log_std[0], mu.shape[:-1])), jnp.squeeze(value, -1)

    def initial_carry(self, batch_shape=()):
        return ()

    def apply_seq(self, params, x, carry):
        dist, value = self.apply(params, x)
        return dist, value, carry


# ---------------------------------------------------------------------------
# Gaussian action distribution helpers — ONE definition for every trainer
# (PPO ratio/entropy, IMPALA V-trace importance weights).  Constants are
# cast to the input dtype: weakly-typed Python floats (and default-dtype
# random sampling) turn f64 under x64 test configs and flip scan-carry
# dtypes downstream.
# ---------------------------------------------------------------------------
HALF_LOG_2PI = 0.9189385332046727        # 0.5 * ln(2*pi)
GAUSS_ENTROPY_CONST = 1.4189385332046727  # 0.5 * ln(2*pi*e)


def normal_logp(x, mu, log_std):
    """Gaussian log-prob in the INPUT dtype."""
    std = jnp.exp(log_std)
    const = jnp.asarray(HALF_LOG_2PI, x.dtype)
    return -0.5 * ((x - mu) / std) ** 2 - log_std - const


def sample_normal(key, dist):
    """Reparameterized sample from a (mu, log_std) pair, in mu's dtype."""
    import jax as _jax

    mu, log_std = dist
    return mu + jnp.exp(log_std) * _jax.random.normal(key, mu.shape, mu.dtype)


def gaussian_entropy(log_std):
    """Mean differential entropy of the (diagonal) Normal."""
    return jnp.mean(jnp.asarray(GAUSS_ENTROPY_CONST, log_std.dtype) + log_std)


def make_trainer_policy(name: str, *, continuous: bool, dtype: Any,
                        kwargs: Dict[str, Any], window: int):
    """The one policy-construction path shared by the trainers: resolves
    per-family kwargs (ring policies need the global window) and picks
    the Gaussian twin (``<name>_continuous``) in continuous mode —
    token-policy twins also need the window for their positional
    embeddings."""
    kw = policy_kwargs_for(name, dict(kwargs), window)
    if continuous:
        if is_token_policy(name):
            kw.setdefault("window", window)
        return make_policy(f"{name}_continuous", dtype=dtype, **kw)
    return make_policy(name, dtype=dtype, **kw)


class GaussianValueHead(nn.Module):
    """Shared continuous actor-critic head: tanh-squashed Normal mean
    over the Box(-1,1,(1,)) action, state-independent learned log-std,
    and the value — the same distribution surface as
    ContinuousMLPPolicy (kept separate there for checkpoint-structure
    stability)."""

    @nn.compact
    def __call__(self, feat):
        mu = nn.tanh(nn.Dense(1, dtype=jnp.float32)(feat))
        # explicit f32 (see ContinuousMLPPolicy: x64 would promote it)
        log_std = self.param(
            "log_std", nn.initializers.constant(-0.5), (1,), jnp.float32
        )
        value = nn.Dense(1, dtype=jnp.float32)(feat)
        return (
            (jnp.squeeze(mu, -1), jnp.broadcast_to(log_std[0], mu.shape[:-1])),
            jnp.squeeze(value, -1),
        )


class ContinuousLSTMPolicy(nn.Module):
    """Gaussian actor-critic on the recurrent trunk (continuous action
    mode x BASELINE config 4's recurrent family)."""

    hidden: int = 256
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, carry):
        x = x.astype(self.dtype)
        x = nn.tanh(nn.Dense(self.hidden, dtype=self.dtype)(x))
        carry, x = nn.OptimizedLSTMCell(self.hidden, dtype=self.dtype)(carry, x)
        dist, value = GaussianValueHead()(x)
        return dist, value, carry

    def initial_carry(self, batch_shape=()):
        return (
            jnp.zeros((*batch_shape, self.hidden), dtype=self.dtype),
            jnp.zeros((*batch_shape, self.hidden), dtype=self.dtype),
        )

    def apply_seq(self, params, x, carry):
        return self.apply(params, x, carry)


class ContinuousRingTransformerPolicy(nn.Module):
    """Gaussian actor-critic over the shared RingTransformerEncoder —
    serves continuous mode for every attention policy (transformer /
    transformer_ring / transformer_ulysses), sequence-parallel modes
    included (seq_sharded_forward works unchanged: same
    window/seq_axis/seq_shards surface)."""

    window: int = 32
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    dtype: Any = jnp.float32
    seq_axis: Optional[str] = None
    seq_shards: int = 1
    sp_backend: str = "ring"

    @nn.compact
    def __call__(self, tokens):
        pooled = RingTransformerEncoder(
            window=self.window, d_model=self.d_model, n_heads=self.n_heads,
            n_layers=self.n_layers, dtype=self.dtype,
            seq_axis=self.seq_axis, seq_shards=self.seq_shards,
            sp_backend=self.sp_backend,
        )(tokens)
        return GaussianValueHead()(pooled)

    def initial_carry(self, batch_shape=()):
        return ()

    def apply_seq(self, params, tokens, carry):
        dist, value = self.apply(params, tokens)
        return dist, value, carry


# policies whose inputs are (window, token_dim) token sequences rather
# than flat vectors — shared by every trainer's encode/init paths
TOKEN_POLICIES = ("transformer", "transformer_ring", "transformer_ulysses",
                  "mla_moe_decoder")


def is_token_policy(name: str) -> bool:
    return name in TOKEN_POLICIES


def policy_kwargs_from(config: Dict[str, Any]) -> Dict[str, Any]:
    """``policy_kwargs`` of a merged config as a dict: a config file gives
    one, the command line gives its JSON text (``--policy_kwargs '{...}'``:
    the widths of a decoder trunk are a nested object)."""
    kwargs = config.get("policy_kwargs") or {}
    if isinstance(kwargs, str):
        import json

        kwargs = json.loads(kwargs)
    return dict(kwargs)


def policy_kwargs_for(name: str, kwargs: Dict[str, Any], window: int) -> Dict[str, Any]:
    """Trainer-side kwarg resolution: the ring policy needs the GLOBAL
    window for its positional embeddings (sliced per shard)."""
    kwargs = dict(kwargs)
    if name in ("transformer_ring", "transformer_ulysses"):
        kwargs.setdefault("window", window)
    return kwargs


def make_policy(name: str, n_actions: int = 3, dtype: Any = jnp.float32, **kw):
    if name == "mlp_continuous":
        return ContinuousMLPPolicy(dtype=dtype, **kw)
    if name == "lstm_continuous":
        return ContinuousLSTMPolicy(dtype=dtype, **kw)
    if name in ("transformer_continuous", "transformer_ring_continuous"):
        return ContinuousRingTransformerPolicy(dtype=dtype, **kw)
    if name == "transformer_ulysses_continuous":
        return ContinuousRingTransformerPolicy(
            dtype=dtype, sp_backend="ulysses", **kw
        )
    if name == "mla_moe_decoder":
        from gymfx_tpu.train.mla_moe_decoder import MlaMoeDecoderPolicy

        return MlaMoeDecoderPolicy(n_actions=n_actions, dtype=dtype, **kw)
    if name == "mlp":
        return MLPPolicy(n_actions=n_actions, dtype=dtype, **kw)
    if name == "lstm":
        return LSTMPolicy(n_actions=n_actions, dtype=dtype, **kw)
    if name == "transformer":
        return TransformerPolicy(n_actions=n_actions, dtype=dtype, **kw)
    if name == "transformer_ring":
        return RingTransformerPolicy(n_actions=n_actions, dtype=dtype, **kw)
    if name == "transformer_ulysses":
        return RingTransformerPolicy(
            n_actions=n_actions, dtype=dtype, sp_backend="ulysses", **kw
        )
    raise ValueError(f"unknown policy {name!r}")
