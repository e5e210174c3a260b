"""The gated delta rule with per-channel decay (Kimi Delta Attention), chunked.

Per head, over the positions of one window, from a zero state:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                               S: (key dim, value dim)

``g_t <= 0`` is the log-decay of each KEY channel.  With
``u_t = beta_t (v_t - (Diag(exp(g_t)) S_{t-1})^T k_t)`` the step is
``S_t = Diag(exp(g_t)) S_{t-1} + k_t u_t^T``, so inside a chunk of C positions
that starts from ``S_0``, with ``G`` the running sum of ``g`` in the chunk:

    (I + A) U = Diag(beta) (V - (K * exp(G)) S_0)
    A[t, i]   = beta_t  sum_c k_tc k_ic exp(G_tc - G_ic)       i <  t
    Aqk[t, i] =         sum_c q_tc k_ic exp(G_tc - G_ic)       i <= t
    O   = (Q * exp(G)) S_0 + Aqk U
    S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

Everything but ``S_0`` is known for every chunk at once (``chunk_parts``: two
products and the inverse of a unit lower-triangular C x C matrix a chunk); only
``chunk_apply`` (four products) runs chunk after chunk.

Decays enter as DIFFERENCES of running log-decays.  ``exp(G_t - G_i)`` is no
product of a row's and a column's factor that stays in range over a chunk (64
steps of -5 are exp(-320)), so rows are taken in blocks of ``SUB`` positions:
against the running sum at its first row a block's rows decay by ``exp(<= 0)``,
the columns before the block too, and the columns inside it grow by at most
``exp((SUB - 1) |g|_max)`` -- exp(75) at the published bound -5, inside
float32 and bfloat16 alike (``MAX_LOG_DECAY_STEP`` is asserted by the layer).
Columns a row may not see are masked before the exponential.

The inverse of the unit lower-triangular ``I + A`` is built by halves
(``unit_lower_inverse``): ``log2(C)`` rounds of two C x C products, no
substitution loop and no power of ``A``.

Products take their operands in the dtype of ``q`` (bfloat16 in one pass,
float32 at ``HIGHEST``) and accumulate in float32; running sums, decays and the
carried state are float32.  The backward pass is JAX's own of this form, a
window at a time: the carried states of the window's chunks are what it keeps
(a 128 x 128 float32 state a head and chunk), everything inside a chunk it has
from the chunk's parts.  The position-by-position recurrence is the
reference's (``gymfx_tpu/reference/hybrid_decoder.py``), not the program's.

This is plain ``jax.numpy``, and no Mosaic kernel, by the chip's A/B (PR 33,
``tools/kda_scan_ab.py``, "TPU v5 lite", [4, 1024, 32, 128] bfloat16): a kernel
pair that walked a (window, head) column of programs through the chunks with
the state in VMEM (the same ``chunk_parts`` / ``chunk_apply`` as the kernel's
body, the backward ``jax.vjp`` of it inside the kernel) took 5.74 ms forward
and 18.72 ms forward + backward against this form's 2.34 and 15.47 ms; XLA
batches a window's 512 chunk-heads into every product, the kernel ran them one
64 x 128 tile at a time.  The kernels are gone; PERF.md section 6 has the numbers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 64
SUB = 16
# the largest |g| a step may have: a block's columns grow by exp((SUB - 1) |g|)
MAX_LOG_DECAY_STEP = 80.0 / (SUB - 1)

_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b, form: str, operand=jnp.float32):
    """``a . b`` over the last two dims (any equal leading dims): ``nn`` plain,
    ``tn`` the first transposed, ``nt`` the second; float32 out."""
    spec = {"nn": "...mk,...kn->...mn", "tn": "...km,...kn->...mn",
            "nt": "...mk,...nk->...mn"}[form]
    return jnp.einsum(spec, a.astype(operand), b.astype(operand),
                      precision=_HIGHEST if operand == jnp.float32 else None,
                      preferred_element_type=jnp.float32)


def _iota(size: int, axis: int):
    return jax.lax.broadcasted_iota(jnp.int32, (size, size), axis)


def unit_lower_inverse(a, operand=jnp.float32):
    """``(I + a)^-1`` for ``a`` (..., C, C) strictly lower triangular, C a
    power of two, by halves: the inverse of ``[[P, 0], [L, Q]]`` is
    ``[[P^-1, 0], [-Q^-1 L P^-1, Q^-1]]``, so from the identity (blocks of one
    row) every round joins neighbouring blocks, ``T <- T - T L T`` with ``L`` the
    lower-left quarters of the joined blocks: ``log2(C)`` rounds, the first of
    them free.  Every factor is a block of ``a`` or of the inverse itself; no
    power of ``a`` is formed (with keys that look alike ``a``'s powers outgrow
    float32 long before ``a^C = 0``)."""
    size = a.shape[-1]
    assert size & (size - 1) == 0, size
    row, col = _iota(size, 0), _iota(size, 1)
    inverse = jnp.where(row == col, 1.0, 0.0) - jnp.where(
        (row == col + 1) & (row % 2 == 1), a, 0.0)
    for level in range(1, size.bit_length() - 1):
        joined = (row >> (level + 1)) == (col >> (level + 1))
        quarter = joined & ((row >> level) % 2 == 1) & ((col >> level) % 2 == 0)
        inverse = inverse - _mm(inverse, _mm(jnp.where(quarter, a, 0.0), inverse, "nn", operand),
                                "nn", operand)
    return inverse


def chunk_parts(q, k, kb, g, operand, sub: int = SUB):
    """What a chunk's step needs beside its start state, from float32 tiles
    (..., C, K) of q, k, ``kb = beta k`` and the log-decay: (q * exp(G),
    kb * exp(G), k * exp(G_C - G), exp(G_C) (..., 1, K), (I + A)^-1, Aqk)."""
    size = q.shape[-2]
    sub = min(sub, size)
    row, col = _iota(size, 0), _iota(size, 1)
    running = jnp.matmul(jnp.where(row >= col, 1.0, 0.0), g, precision=_HIGHEST)
    a_rows, qk_rows = [], []
    for lo in range(0, size, sub):
        hi = lo + sub
        start = running[..., lo:lo + 1, :]
        shrink = jnp.exp(running[..., lo:hi, :] - start)
        seen = jax.lax.broadcasted_iota(jnp.int32, running.shape[-2:], 0) < hi
        keys = k * jnp.exp(jnp.where(seen, start - running, -1e30))
        lefts = jnp.concatenate([kb[..., lo:hi, :] * shrink, q[..., lo:hi, :] * shrink],
                                axis=-2)
        both = _mm(lefts, keys, "nt", operand)
        a_rows.append(both[..., :sub, :])
        qk_rows.append(both[..., sub:, :])
    a = jnp.where(row > col, jnp.concatenate(a_rows, axis=-2), 0.0)
    qk = jnp.where(row >= col, jnp.concatenate(qk_rows, axis=-2), 0.0)
    decay = jnp.exp(running)
    end = running[..., size - 1:, :]
    return (q * decay, kb * decay, k * jnp.exp(end - running), jnp.exp(end),
            unit_lower_inverse(a, operand), qk)


def chunk_apply(state, q_decayed, kb_decayed, k_to_end, decay_to_end, inverse, qk, vb,
                operand):
    """One chunk from ``state`` (..., V, K), the state TRANSPOSED (its decay is
    then a row's scale): (the chunk's outputs (..., C, V), the state after)."""
    u = _mm(inverse, vb - _mm(kb_decayed, state, "nt", operand), "nn", operand)
    out = _mm(q_decayed, state, "nt", operand) + _mm(qk, u, "nn", operand)
    return out, state * decay_to_end + _mm(u, k_to_end, "tn", operand)


def _window_scan(q, k, v, g, beta, chunk: int):
    """One window: q, k, g (W, H, K), v (W, H, V), beta (W, H) -> (W, H, V)."""
    window, heads, _ = q.shape
    dtype = q.dtype
    operand = jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32
    chunks = -(-window // chunk)

    def tiles(x):
        """(W, H, D) -> float32 (chunks, H, chunk, D)."""
        x = jnp.pad(x.astype(jnp.float32), ((0, chunks * chunk - window), (0, 0), (0, 0)))
        return x.reshape(chunks, chunk, heads, -1).transpose(0, 2, 1, 3)

    q, k, v, g, beta = (tiles(x) for x in (q, k, v, g, beta[..., None]))
    parts = chunk_parts(q, k, k * beta, g, operand)

    def step(state, xs):
        out, state = chunk_apply(state, *xs, operand)
        return state, out

    state = jnp.zeros((heads, v.shape[-1], k.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(step, state, (*parts, v * beta))
    out = out.transpose(0, 2, 1, 3).reshape(chunks * chunk, heads, -1)
    return out[:window].astype(dtype)


def kda_chunk_scan(q, k, v, g, beta, *, chunk: int = CHUNK):
    """``o`` (B, W, H, V) of the recurrence above for q, k, g (B, W, H, K),
    v (B, W, H, V), beta (B, W, H); every window from a zero state.  A window
    that is no multiple of ``chunk`` is padded behind its last position (a
    position there changes no output before it).  Out in ``q.dtype``.

    A window at a time (``lax.map``), each under ``jax.checkpoint``: the
    backward pass then holds ONE window's parts and chunk states (0.5 GB at
    1,024 x 32 x 128) and walks that window again, where the whole batch's are
    2.2 GB at four windows."""
    one = jax.checkpoint(functools.partial(_window_scan, chunk=chunk))
    return jax.lax.map(lambda x: one(*x), (q, k, v, g, beta))


def causal_conv(x, taps):
    """Depthwise causal convolution over positions: x (..., W, channels), taps
    (taps, channels); ``y_t = sum_j taps[j] x_{t - (taps - 1) + j}``, zeros
    before the window's first position."""
    n = taps.shape[0]
    padded = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(n - 1, 0), (0, 0)])
    window = x.shape[-2]
    return sum(padded[..., j:j + window, :] * taps[j] for j in range(n))
