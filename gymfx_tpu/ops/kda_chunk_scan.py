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
float32 at ``HIGHEST``) and accumulate in float32; running sums, decays, the
carried state and its cotangent are float32.  The position-by-position
recurrence is the reference's (``gymfx_tpu/reference/hybrid_decoder.py``), not
the program's.

The backward pass is the scan's own (``jax.custom_vjp``, PR 34), of the same
chunked form, a window at a time.  Between the passes it keeps the five inputs
and nothing else.  For a window it forms the chunks' parts again, walks the
chunks forward for their start states, ``w = vb - Kd S_0^T`` and ``u = T w``
(``chunk_state``: three of the forward's five products), and then walks them
ONCE in reverse carrying the state's cotangent (``chunk_apply_transposed``: ten
products a chunk, none on a stacked residual):

    du   = Aqk^T dO + Ke dS^T            dw = T^T du
    dS_0 = dS * exp(G_C) + dO^T Qd - dw^T Kd
    dQd = dO S_0,  dKd = -dw S_0,  dKe = u dS,  d exp(G_C) = sum_V dS * S_0
    dT  = du w^T,  dAqk = dO u^T,  dvb = dw

The parts' cotangents go back to q, k, v, g, beta for all chunks at once:
``jax.vjp`` of ``chunk_products``, and ``dA = -T^T dT T^T`` for the inverse in
the place of a walk back through its rounds.  What JAX derives from the
``lax.scan`` instead stacks every chunk's operands (0.5 GB a window) and walks
the forward a second time under the ``jax.checkpoint`` that bounded it: 15.5 ms
a call of [4, 1024, 32, 128] bfloat16 against 12.6 (``tools/kda_scan_ab.py``,
"TPU v5 lite", PR 34).  Keeping the chunks' start states from the forward pass
saved 0.2 ms a call more and cost the train step 0.26 GB at its peak, which is
in the expert layer's backward pass, where a block's residuals wait: dropped.
The log-decay's cotangent is written over the log-decay's own rows
(``_scan_bwd``): as one more stacked output of the loop over windows XLA
allocated it before the block's backward pass began, 0.13 GB at the same peak.

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

import jax
import jax.numpy as jnp

from gymfx_tpu.telemetry import scopes

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


def chunk_products(q, k, kb, g, operand, sub: int = SUB):
    """What a chunk's step needs beside its start state, short of the inverse,
    from float32 tiles (..., C, K) of q, k, ``kb = beta k`` and the log-decay:
    (q * exp(G), kb * exp(G), k * exp(G_C - G), exp(G_C) (..., 1, K), A, Aqk)."""
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
    return q * decay, kb * decay, k * jnp.exp(end - running), jnp.exp(end), a, qk


def chunk_parts(q, k, kb, g, operand, sub: int = SUB):
    """``chunk_products`` with ``(I + A)^-1`` in the place of ``A``."""
    *decayed, a, qk = chunk_products(q, k, kb, g, operand, sub)
    return (*decayed, unit_lower_inverse(a, operand), qk)


def chunk_state(state, kb_decayed, k_to_end, decay_to_end, inverse, vb, operand):
    """A chunk's ``w = vb - Kd S^T`` and ``u = T w`` from ``state`` (..., V, K),
    the state TRANSPOSED (its decay is then a row's scale), and the state
    after: (w, u (..., C, V), the state after)."""
    w = vb - _mm(kb_decayed, state, "nt", operand)
    u = _mm(inverse, w, "nn", operand)
    return w, u, state * decay_to_end + _mm(u, k_to_end, "tn", operand)


def chunk_apply(state, q_decayed, kb_decayed, k_to_end, decay_to_end, inverse, qk, vb,
                operand):
    """One chunk from ``state``: (the chunk's outputs (..., C, V), the state
    after)."""
    _, u, after = chunk_state(state, kb_decayed, k_to_end, decay_to_end, inverse, vb, operand)
    return _mm(q_decayed, state, "nt", operand) + _mm(qk, u, "nn", operand), after


def chunk_apply_transposed(dstate, dout, state, w, u, q_decayed, kb_decayed, k_to_end,
                           decay_to_end, inverse, qk, operand):
    """``chunk_apply``'s derivative turned round: from the cotangents ``dout``
    (..., C, V) of a chunk's outputs and ``dstate`` (..., V, K) of the state
    after, (the cotangent of the state it started from, the cotangents of
    ``chunk_apply``'s seven operands in its order).  Ten products."""
    du = _mm(qk, dout, "tn", operand) + _mm(k_to_end, dstate, "nt", operand)
    dw = _mm(inverse, du, "tn", operand)
    before = (dstate * decay_to_end + _mm(dout, q_decayed, "tn", operand)
              - _mm(dw, kb_decayed, "tn", operand))
    return before, (_mm(dout, state, "nn", operand), -_mm(dw, state, "nn", operand),
                    _mm(u, dstate, "nn", operand),
                    jnp.sum(dstate * state, axis=-2, keepdims=True),
                    _mm(du, w, "nt", operand), _mm(dout, u, "nt", operand), dw)


def _operand(dtype):
    return jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32


def _tiles(x, chunk: int):
    """(W, H, D) -> float32 (chunks, H, chunk, D), zeros behind the window."""
    window, heads, _ = x.shape
    chunks = -(-window // chunk)
    x = jnp.pad(x.astype(jnp.float32), ((0, chunks * chunk - window), (0, 0), (0, 0)))
    return x.reshape(chunks, chunk, heads, -1).transpose(0, 2, 1, 3)


def _window_parts(q, k, v, g, beta, chunk: int, build):
    """A window's tiles through ``build`` (``chunk_parts`` or ``chunk_products``),
    and ``beta v`` behind them."""
    operand = _operand(q.dtype)
    q, k, v, g, beta = (_tiles(x, chunk) for x in (q, k, v, g, beta[..., None]))
    return (*build(q, k, k * beta, g, operand), v * beta)


def _window_scan(q, k, v, g, beta, chunk: int):
    """One window: q, k, g (W, H, K), v (W, H, V), beta (W, H) -> (W, H, V)."""
    window, heads, _ = q.shape
    dtype = q.dtype
    operand = _operand(dtype)
    parts = _window_parts(q, k, v, g, beta, chunk, chunk_parts)

    def step(state, xs):
        out, state = chunk_apply(state, *xs, operand)
        return state, out

    state = jnp.zeros((heads, v.shape[-1], k.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(step, state, parts)
    out = out.transpose(0, 2, 1, 3).reshape(-1, heads, out.shape[-1])
    return out[:window].astype(dtype)


def _window_scan_transposed(q, k, v, g, beta, dout, chunk: int):
    """One window's cotangents of q, k, v, g, beta, in their shapes and dtypes,
    from the outputs' ``dout`` (W, H, V): the parts of all chunks again; the
    chunks' start states, ``w`` and ``u`` by a walk of ``chunk_state`` (three of
    the forward's five products); ONE reverse walk of ``chunk_apply_transposed``
    carrying the state's cotangent; the parts' cotangents back to the inputs,
    all chunks at once."""
    operand = _operand(q.dtype)
    parts, parts_back = jax.vjp(
        lambda *x: _window_parts(*x, chunk, chunk_products), q, k, v, g, beta)
    *decayed, decay_to_end, a, qk, vb = parts
    inverse = unit_lower_inverse(a, operand)
    # the products' operands ONCE, ahead of both walks, in the dtype _mm gives
    # them (the same values; half the bytes a step reads in bfloat16)
    q_decayed, kb_decayed, k_to_end, inverse, qk, dout = (
        x.astype(operand) for x in (*decayed, inverse, qk, _tiles(dout, chunk)))

    def forth(state, xs):
        w, u, after = chunk_state(state, *xs, operand)
        return after, (state, w, u)

    def back(dstate, xs):
        return chunk_apply_transposed(dstate, *xs, operand)

    zero = jnp.zeros((q.shape[1], v.shape[-1], k.shape[-1]), jnp.float32)
    _, walked = jax.lax.scan(forth, zero, (kb_decayed, k_to_end, decay_to_end, inverse, vb))
    _, dparts = jax.lax.scan(
        back, zero,
        (dout, *walked, q_decayed, kb_decayed, k_to_end, decay_to_end, inverse, qk),
        reverse=True)
    # d(I + A)^-1 = -T^T dT T^T; parts_back keeps its strictly lower part
    da = -_mm(_mm(inverse, dparts[4], "tn", operand), inverse, "nt", operand)
    return parts_back((*dparts[:4], da, *dparts[5:]))


def scan_forward(q, k, v, g, beta, chunk: int = CHUNK):
    """``kda_chunk_scan`` without its own derivative: a window at a time."""
    return jax.lax.map(lambda x: _window_scan(*x, chunk), (q, k, v, g, beta))


_scan = jax.custom_vjp(scan_forward, nondiff_argnums=(5,))


def _scan_fwd(q, k, v, g, beta, chunk):
    return scan_forward(q, k, v, g, beta, chunk), (q, k, v, g, beta)


def _scan_bwd(chunk, kept, dout):
    """A window at a time.  The log-decay's cotangent, the one float32 array of
    the inputs' size, is written over the log-decay's own rows: as a stacked
    output XLA allocated it before the block's backward pass began, and it lay
    at the train step's memory peak."""
    q, k, v, g, beta = kept

    def window(g, xs):
        i, *one = xs
        dq, dk, dv, dg, dbeta = _window_scan_transposed(
            *one[:3], jax.lax.dynamic_index_in_dim(g, i, keepdims=False), *one[3:], chunk)
        return jax.lax.dynamic_update_index_in_dim(g, dg, i, 0), (dq, dk, dv, dbeta)

    with jax.named_scope(scopes.KDA_SCAN):
        dg, (dq, dk, dv, dbeta) = jax.lax.scan(
            window, g, (jnp.arange(g.shape[0]), q, k, v, beta, dout))
    return dq, dk, dv, dg, dbeta


_scan.defvjp(_scan_fwd, _scan_bwd)


def kda_chunk_scan(q, k, v, g, beta, *, chunk: int = CHUNK):
    """``o`` (B, W, H, V) of the recurrence above for q, k, g (B, W, H, K),
    v (B, W, H, V), beta (B, W, H); every window from a zero state.  A window
    that is no multiple of ``chunk`` is padded behind its last position (a
    position there changes no output before it).  Out in ``q.dtype``.  The
    whole call, both directions, is the ``kda_scan`` part of its layer
    (telemetry/scopes.py)."""
    with jax.named_scope(scopes.KDA_SCAN):
        return _scan(q, k, v, g, beta, chunk)


def causal_conv(x, taps):
    """Depthwise causal convolution over positions: x (..., W, channels), taps
    (taps, channels); ``y_t = sum_j taps[j] x_{t - (taps - 1) + j}``, zeros
    before the window's first position: the ``causal_conv`` part of its layer
    (telemetry/scopes.py)."""
    n = taps.shape[0]
    with jax.named_scope(scopes.CAUSAL_CONV):
        padded = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(n - 1, 0), (0, 0)])
        window = x.shape[-2]
        return sum(padded[..., j:j + window, :] * taps[j] for j in range(n))
