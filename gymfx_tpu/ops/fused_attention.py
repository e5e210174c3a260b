"""Pallas TPU kernel: fused single-pass attention for policy windows.

VERDICT r4 weak #5: the transformer_ring policy's single-device path
computed plain ``softmax(QK^T)V`` through XLA, which materializes the
``(envs, heads, W, W)`` score tensor in HBM — at window 256 x 8192 envs
that is ~4 GB of score traffic per forward, and the long-context bench
row ran at 0.2x the per-chip target.  This kernel computes a BLOCK of
envs' whole-window attention per program in a single VMEM-resident
pass (flash-attention's insight specialized to policy windows:
W <= 1024 means the full W x W score block FITS in VMEM, so no
online-softmax streaming is needed — one exp, one normalize, zero HBM
score traffic).

Granularity matters twice here:
  * env blocks (``_env_block``) amortize per-program overhead — one
    program per (env, head) measured SLOWER than XLA (dispatch
    overhead beats the HBM saving at 16k tiny programs);
  * a ``jax.custom_batching.custom_vmap`` rule folds the trainers'
    per-env ``vmap`` into the blocked kernel — pallas' default
    batching rule would add a size-1 grid dimension per env and
    recreate exactly the tiny-program problem.

Numerics run in float32 inside the kernel regardless of the policy
dtype, like XLA's f32 matmul accumulation on bf16 inputs.
Differentiable: the backward is a fused Pallas kernel too
(``_bwd_kernel``) — it saves no score tensor, recomputes the softmax
probabilities from q/k inside VMEM (the standard flash-attention
recompute trade: extra forward FLOPs on the rarer update pass, zero
HBM score traffic), then forms dV, dS, dQ, dK in the same
env-blocked single pass.  The plain-XLA twin
(``parallel.ring_attention.full_attention``) is the parity oracle
for BOTH directions (tests/test_ops.py), not part of the compiled
gradient.

``interpret=None`` resolves in ``ops/dispatch.py``: compiled on a TPU,
the pallas interpreter elsewhere, so tests run on CPU; the plain-XLA
twin remains the parity oracle and serves windows beyond 1024.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gymfx_tpu.ops.dispatch import resolve_interpret
from gymfx_tpu.telemetry.scopes import KERNEL_ATTENTION_BWD, KERNEL_ATTENTION_FWD

# beyond this window the W x W f32 score blocks (plus q/k/v) stop
# fitting comfortably in ~16 MB VMEM; longer sequences are the ring /
# Ulysses backends' territory anyway (parallel/ring_attention.py)
MAX_FUSED_WINDOW = 1024

# below this window the kernel LOSES to XLA: at W=32 the measured A/B
# on the v5e chip was 30.8k vs 145.9k env-steps/s — the per-program
# work is tiny, and the (B,S,H,D)<->(B,H,S,D) transposes around the
# call cost more than the (small) score tensors ever did.  The fused
# path only pays off where score HBM traffic is the wall (W^2 scaling):
# measured 1.43x op-level at W=256.  Callers (policies.py
# dense_window_attention) route short windows to plain XLA.
MIN_FUSED_WINDOW = 192


# what the env block is sized to hold, and the scoped-VMEM limit asked
# of Mosaic (its default on v5e is 16 MiB of the core's 128 MiB).  The
# gap is headroom for the compiler's own temporaries; the smallest block
# (one env) at MAX_FUSED_WINDOW in float32 needs it: its backward with
# HIGHEST-precision dots allocates 16.8 MB.
_VMEM_BUDGET = 12 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=32 * 1024 * 1024)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _env_block(batch: int, window: int, head_dim: int, itemsize: int,
               io_blocks: int, score_blocks_live: int) -> int:
    """Envs per program: amortize program overhead while one program's
    VMEM stays inside ``_VMEM_BUDGET``.  Counted per env, at the size
    Mosaic really allocates:

      * the ``io_blocks`` (S, D) q/k/v/o (backward: q/k/v/g/dq/dk/dv)
        faces — D lane-padded to 128, S padded to the dtype's sublane
        tile, and each DOUBLE-buffered by the pipeline;
      * the live f32 score-shaped values (forward: scores; backward:
        scores/p, dp, ds), S x S with the key axis lane-padded.

    The forward at (256 envs, window 256, 4 x 32) in float32 is the
    shape the old score-only budget got wrong: 16 envs of q/k/v/o are
    16.8 MB before a single score is computed."""
    sublane = 8 * max(1, 4 // itemsize)
    io = (
        io_blocks * _round_up(window, sublane) * _round_up(head_dim, 128)
        * itemsize * 2
    )
    scores = (
        score_blocks_live * _round_up(window, 8) * _round_up(window, 128) * 4
    )
    budget = max(1, _VMEM_BUDGET // (io + scores))
    for eb in (16, 8, 4, 2, 1):
        if eb <= budget and batch % eb == 0:
            return eb
    return 1


def _precision(ref):
    """MXU precision of the in-kernel matmuls.  Mosaic, like XLA:TPU,
    runs an f32 x f32 dot as ONE bf16 pass unless asked otherwise —
    measured on the chip (PR 22) that put the float32 kernel 8e-3 from
    ``full_attention``, not the 2e-5 its tests hold it to.  A float32
    policy gets true f32 attention (``HIGHEST``); bf16 inputs are exact
    in one pass and keep the default."""
    return jax.lax.Precision.HIGHEST if ref.dtype == jnp.float32 else None


def _kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool):
    prec = _precision(q_ref)
    q = q_ref[:, 0].astype(jnp.float32)   # (eb, S, D)
    k = k_ref[:, 0].astype(jnp.float32)
    v = v_ref[:, 0].astype(jnp.float32)
    scores = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=prec,
    ) * scale                              # (eb, S, S)
    if causal:
        s = scores.shape[-1]
        row = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
        scores = jnp.where((row >= col)[None], scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    num = jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=prec,
    )                                      # (eb, S, D)
    out = num / jnp.sum(p, axis=-1, keepdims=True)
    o_ref[:, 0] = out.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, dq_ref, dk_ref, dv_ref, *,
                scale: float, causal: bool):
    """VMEM-resident attention backward: recompute the score block from
    q/k (cheaper than ever writing it to HBM), then the standard
    softmax-attention gradients — dV = P^T dO, dP = dO V^T,
    dS = P (dP - rowsum(dP P)), dQ = scale dS K, dK = scale dS^T Q."""
    prec = _precision(q_ref)
    q = q_ref[:, 0].astype(jnp.float32)   # (eb, S, D)
    k = k_ref[:, 0].astype(jnp.float32)
    v = v_ref[:, 0].astype(jnp.float32)
    g = g_ref[:, 0].astype(jnp.float32)
    scores = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=prec,
    ) * scale
    if causal:
        s = scores.shape[-1]
        row = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
        scores = jnp.where((row >= col)[None], scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)      # (eb, Sq, Sk)
    dv = jax.lax.dot_general(
        p, g, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=prec,
    )                                               # (eb, Sk, D)
    dp = jax.lax.dot_general(
        g, v, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=prec,
    )                                               # (eb, Sq, Sk)
    delta = jnp.sum(dp * p, axis=-1, keepdims=True)
    ds = p * (dp - delta) * scale
    dq = jax.lax.dot_general(
        ds, k, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=prec,
    )
    dk = jax.lax.dot_general(
        ds, q, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=prec,
    )
    dq_ref[:, 0] = dq.astype(dq_ref.dtype)
    dk_ref[:, 0] = dk.astype(dk_ref.dtype)
    dv_ref[:, 0] = dv.astype(dv_ref.dtype)


def _backward_batched(q, k, v, g, causal: bool, interpret: bool):
    """Fused backward on (B, S, H, D) primals + cotangent."""
    b, s, h, d = q.shape
    eb = _env_block(b, s, d, q.dtype.itemsize, io_blocks=7,
                    score_blocks_live=3)
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(_bwd_kernel, scale=scale, causal=causal)
    spec = pl.BlockSpec((eb, 1, s, d), lambda i, j: (i, j, 0, 0))
    call = pl.pallas_call(
        kernel,
        grid=(b // eb, h),
        in_specs=[spec] * 4,
        out_specs=[spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((b, h, s, d), q.dtype)] * 3,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=KERNEL_ATTENTION_BWD,
    )
    sw = lambda x: jnp.swapaxes(x, 1, 2)  # noqa: E731
    dq, dk, dv = call(sw(q), sw(k), sw(v), sw(g))
    return sw(dq), sw(dk), sw(dv)


def _forward_batched(q, k, v, causal: bool, interpret: bool):
    """Fused pass on (B, S, H, D) inputs."""
    b, s, h, d = q.shape
    eb = _env_block(b, s, d, q.dtype.itemsize, io_blocks=4,
                    score_blocks_live=1)
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(_kernel, scale=scale, causal=causal)
    # (B, H, S, D) layout: heads and env blocks ride the grid; Mosaic
    # requires the last two block dims to tile (8, 128) or span the
    # array, so the (S, D) face stays whole
    call = pl.pallas_call(
        kernel,
        grid=(b // eb, h),
        in_specs=[pl.BlockSpec((eb, 1, s, d), lambda i, j: (i, j, 0, 0))] * 3,
        out_specs=pl.BlockSpec((eb, 1, s, d), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=KERNEL_ATTENTION_FWD,
    )
    out = call(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
    )
    return jnp.swapaxes(out, 1, 2)


@functools.lru_cache(maxsize=None)
def _make(causal: bool, interpret: bool):
    from jax.custom_batching import custom_vmap

    @jax.custom_vjp
    def attend_batched(q, k, v):           # (B, S, H, D)
        return _forward_batched(q, k, v, causal, interpret)

    def fwd(q, k, v):
        return attend_batched(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        return _backward_batched(q, k, v, g, causal, interpret)

    attend_batched.defvjp(fwd, bwd)

    @custom_vmap
    def attend_raw(q, k, v):               # (S, H, D)
        return _forward_batched(
            q[None], k[None], v[None], causal, interpret
        )[0]

    @attend_raw.def_vmap
    def _attend_vmap_rule(axis_size, in_batched, q, k, v):
        if not all(in_batched):
            # replicate any unbatched operand along the vmapped axis,
            # each with its OWN trailing shape
            q, k, v = (
                x if bat else jnp.broadcast_to(x[None], (axis_size, *x.shape))
                for x, bat in zip((q, k, v), in_batched)
            )
        return attend_batched(q, k, v), True

    # the backward gets the same vmap-collapse treatment: without it,
    # grad-of-vmap (the training update) would push the pallas backward
    # through the default size-1-grid batching rule — the tiny-program
    # regime the env blocks exist to avoid
    @custom_vmap
    def bwd_raw(q, k, v, g):               # (S, H, D)
        dq, dk, dv = _backward_batched(
            q[None], k[None], v[None], g[None], causal, interpret
        )
        return dq[0], dk[0], dv[0]

    @bwd_raw.def_vmap
    def _bwd_vmap_rule(axis_size, in_batched, q, k, v, g):
        if not all(in_batched):
            q, k, v, g = (
                x if bat else jnp.broadcast_to(x[None], (axis_size, *x.shape))
                for x, bat in zip((q, k, v, g), in_batched)
            )
        return (
            _backward_batched(q, k, v, g, causal, interpret),
            (True, True, True),
        )

    # custom_vmap alone does not support reverse AD; the outer
    # custom_vjp makes every transform order work — vmap(attend) hits
    # the collapse rule, grad(attend) and grad(vmap(attend)) hit the
    # fused backward kernel
    @jax.custom_vjp
    def attend(q, k, v):
        return attend_raw(q, k, v)

    def afwd(q, k, v):
        return attend(q, k, v), (q, k, v)

    def abwd(res, g):
        q, k, v = res
        return bwd_raw(q, k, v, g)

    attend.defvjp(afwd, abwd)
    return attend, attend_batched


def fused_window_attention(q, k, v, *, causal: bool = False,
                           interpret: bool | None = None):
    """Exact attention for (..., W, H, D) q/k/v with the score blocks
    kept in VMEM.  Any leading batch dims (flattened into the kernel's
    env-block grid).  Differentiable (fused Pallas backward that
    recomputes the probabilities in VMEM — see module docstring).
    Returns (..., W, H, D) in the input dtype."""
    interpret = resolve_interpret(interpret)
    *batch, s, h, d = q.shape
    if s > MAX_FUSED_WINDOW:
        raise ValueError(
            f"fused_window_attention holds whole {s}x{s} score blocks "
            f"in VMEM; windows beyond {MAX_FUSED_WINDOW} belong to the "
            "ring/Ulysses sequence-parallel backends"
        )
    attend, attend_batched = _make(bool(causal), bool(interpret))
    if not batch:
        return attend(q, k, v)
    flat = lambda x: x.reshape(-1, s, h, d)  # noqa: E731
    out = attend_batched(flat(q), flat(k), flat(v))
    return out.reshape(*batch, s, h, d)
