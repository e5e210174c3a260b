"""Pallas TPU kernel: sort-free LOB stream matching.

``lob/book.process_stream`` is a ``lax.scan`` of ``lax.switch`` message
dispatch whose hot op is an ``argsort`` over the flattened price-time
keys — a sort the TPU vector unit has no native lowering for, so XLA
serializes it through expensive generic sorts per message.  This module
re-derives every half-book primitive in sort-free dense int32 algebra
so the whole stream runs as ONE pallas program per 128 books (one book
per lane, grid over lane blocks, ``fori_loop`` over messages, book
state resident in VMEM — see "Layout" below):

  * matching: each slot's fill is ``clip(take - prior, 0, avail)``
    where ``prior`` is the liquidity strictly ahead of it in price-time
    priority — the sum over strictly-better level keys plus the FIFO
    prefix within its own level.  Identical to the sorted cumsum walk
    because live levels never share a price, so flattened keys are
    unique wherever liquidity exists;
  * queue compaction: each live slot moves to its rank = count of live
    slots before it (exclusive prefix sum) — the stable
    ``argsort(qty == 0)`` without the sort;
  * resting/cancelling: first-free-index selects become masked-min +
    one-hot dense updates.

Message dispatch is dense too: every branch (add buy/sell, cancel,
market) is computed and the result selected by kind/side — exact,
because all branches are pure int32 and a zero-quantity match /
zero-oid cancel / zero-lot rest is a bitwise no-op on an invariant
book (front-compacted queues, zero oid in empty slots, zero price on
empty levels).  ``tests/test_lob_match_kernel.py`` pins exact int32
parity against ``book.process_stream`` message-for-message.

Dispatch: ``lob/venue.execute_bar`` (per-bar seed stream) and
``bench.py --lob`` behind the ``lob_match_kernel`` off|on|interpret
knob (``ops/dispatch.py``) — "off" keeps the argsort engine (the
oracle), "on" is this kernel compiled on a TPU, or its error, and the
oracle on a CPU (both are exact), "interpret" forces the pallas
interpreter for CPU parity tests.  The intrabar agent flow scan keeps the oracle engine: its
per-message ``lax.cond`` stop-trigger logic is agent bookkeeping, not
matching.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from gymfx_tpu.ops.dispatch import resolve_interpret
from gymfx_tpu.lob.book import (
    AGENT_OID,
    MSG_ADD,
    MSG_CANCEL,
    MSG_MARKET,
    PRICE_CAP,
    BookState,
    FillRecord,
    Messages,
)

_FILL_COLS = len(FillRecord._fields)
_LANES = 128

# Layout.  Everything below is written for BOOKS ON THE LANE AXIS: a
# half book is ``price`` (D, L) plus ``qty``/``oid`` as Q-tuples of
# (D, L) slot faces, and every per-book scalar (a message field, a fill
# statistic) is a (1, L) row — L independent books advance in lockstep,
# one per lane.  Every value is a 2-D int32 tile and every reduction
# runs over levels (sublanes) or the static slot tuple, which is the
# form Mosaic lowers; one book per program with (D,) vectors and scalar
# reductions does not even tile ((1, depth) blocks of a (books, depth)
# array).  The single-book XLA twin is the same code at L = 1.


def _iota_levels(shape):
    # 1D iota is not allowed on TPU pallas; broadcasted_iota always is
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0)


def _sum_slots(xs):
    return functools.reduce(lambda a, b: a + b, xs)


def _sum_levels(x):
    return jnp.sum(x, axis=0, keepdims=True, dtype=jnp.int32)      # (1, L)


def _any_levels(mask):
    return _sum_levels(mask.astype(jnp.int32)) > 0


def _prefix_slots(xs):
    """Exclusive prefix sum over the slot tuple (FIFO order)."""
    out, acc = [], jnp.zeros_like(xs[0])
    for x in xs:
        out.append(acc)
        acc = acc + x
    return tuple(out)


def _first_true(mask, size):
    """Level index of the first True per book (``size`` when none) —
    ``argmax`` on bool without the argmax: masked min over the iota."""
    return jnp.min(
        jnp.where(mask, _iota_levels(mask.shape), size), axis=0,
        keepdims=True,
    )


def _compact_dense(qty, oid):
    """``book._compact`` without the argsort: each live slot moves to
    its rank (count of live slots before it); dead slots zero-fill.
    Exact: ranks of live slots are distinct and increasing, which IS
    the stable sort order."""
    live = tuple(q > 0 for q in qty)
    rank = _prefix_slots(tuple(m.astype(jnp.int32) for m in live))
    new_qty, new_oid = [], []
    for col in range(len(qty)):
        nq = jnp.zeros_like(qty[0])
        no = jnp.zeros_like(oid[0])
        for j in range(len(qty)):
            m = live[j] & (rank[j] == col)
            nq = jnp.where(m, qty[j], nq)
            no = jnp.where(m, oid[j], no)
        new_qty.append(nq)
        new_oid.append(no)
    return tuple(new_qty), tuple(new_oid)


def _reset_empty_levels(price, qty):
    return jnp.where(_sum_slots(qty) > 0, price, 0)


def _match_half(price, qty, oid, take_qty, limit, against_asks: bool):
    """``book._match_half`` with the sorted cumsum walk replaced by the
    prior-liquidity form: fill_j = clip(take - prior_j, 0, avail_j),
    prior_j = liquidity at strictly better price-time keys.  Bitwise
    identical because keys are unique wherever avail > 0."""
    active = price > 0
    if against_asks:
        eligible = active & (price <= limit)
        level_key = jnp.where(eligible, price, PRICE_CAP)
    else:
        eligible = active & (price >= limit)
        level_key = jnp.where(eligible, PRICE_CAP - price, PRICE_CAP)
    avail = tuple(jnp.where(eligible, q, 0) for q in qty)

    level_avail = _sum_slots(avail)                                 # (D, L)
    # liquidity at strictly better level keys: a static walk over the
    # levels (each step one sublane-broadcast compare) instead of the
    # (D, D) outer compare
    ahead_levels = jnp.zeros_like(level_avail)
    for lv in range(price.shape[0]):
        ahead_levels = ahead_levels + jnp.where(
            level_key[lv:lv + 1] < level_key, level_avail[lv:lv + 1], 0
        )
    fill = tuple(
        jnp.clip(take_qty - (ahead_levels + before), 0, a)
        for before, a in zip(_prefix_slots(avail), avail)
    )

    # int32 adds wrap the same in any order (the book.py x64 rule pins
    # the dtype, the order is free)
    level_fill = _sum_slots(fill)
    filled = _sum_levels(level_fill)
    value = _sum_levels(level_fill * price)
    events = _sum_levels(_sum_slots(tuple((f > 0).astype(jnp.int32)
                                          for f in fill)))
    agent_fill = _sum_slots(tuple(
        jnp.where((o == AGENT_OID) & (f > 0), f, 0)
        for o, f in zip(oid, fill)
    ))
    agent_qty = _sum_levels(agent_fill)
    agent_value = _sum_levels(agent_fill * price)
    touched = level_fill > 0
    pmin = jnp.min(jnp.where(touched, price, PRICE_CAP), axis=0,
                   keepdims=True)
    pmax = jnp.max(jnp.where(touched, price, 0), axis=0, keepdims=True)

    new_qty = tuple(q - f for q, f in zip(qty, fill))
    new_oid = tuple(jnp.where(q > 0, o, 0) for q, o in zip(new_qty, oid))
    new_qty, new_oid = _compact_dense(new_qty, new_oid)
    new_price = _reset_empty_levels(price, new_qty)
    stats = (filled, value, events, agent_qty, agent_value, pmin, pmax)
    return (new_price, new_qty, new_oid), stats


def _rest_half(price, qty, oid, p, q, o):
    """``book._rest_half`` with the (li, si) scatter as a one-hot dense
    update.  li = D (empty one-hot, no write) when neither an existing
    level nor a free one exists — the original's ``can`` gate."""
    D, Q = price.shape[0], len(qty)
    has_level = (price == p) & (price > 0)
    level_free = _sum_slots(qty) == 0
    any_has = _any_levels(has_level)
    li = jnp.where(
        any_has, _first_true(has_level, D), _first_true(level_free, D)
    )
    can = (q > 0) & (any_has | _any_levels(level_free))
    lvl = _iota_levels(price.shape) == li
    free = tuple(x == 0 for x in qty)
    si_per_level = jnp.full_like(price, Q)
    for j in reversed(range(Q)):
        si_per_level = jnp.where(free[j], j, si_per_level)
    si = _sum_levels(jnp.where(lvl, si_per_level, 0))
    can = can & _any_levels(lvl & (si_per_level < Q))
    rested = jnp.where(can, q, 0)
    put = can & lvl
    qty = tuple(jnp.where(put & (si == j), q, x) for j, x in enumerate(qty))
    oid = tuple(jnp.where(put & (si == j), o, x) for j, x in enumerate(oid))
    price = jnp.where(put, p, price)
    return (price, qty, oid), rested


def _cancel_half(price, qty, oid, target_oid):
    hit = tuple(
        (o == target_oid) & (x > 0) & (target_oid != 0)
        for o, x in zip(oid, qty)
    )
    removed = _sum_levels(
        _sum_slots(tuple(jnp.where(m, x, 0) for m, x in zip(hit, qty)))
    )
    qty = tuple(jnp.where(m, 0, x) for m, x in zip(hit, qty))
    oid = tuple(jnp.where(m, 0, x) for m, x in zip(hit, oid))
    qty, oid = _compact_dense(qty, oid)
    price = _reset_empty_levels(price, qty)
    return (price, qty, oid), removed


def _process_message_dense(halves, msg):
    """``book.process_message`` with the lax.switch/cond dispatch as
    dense compute-all-branches-and-select — every branch is pure int32
    and the inapplicable ones are bitwise no-ops (zero take / zero rest
    / zero cancel target) on an invariant book."""
    bp, bq, bo, ap, aq, ao = halves
    kind, side, price, qty, oid = msg
    k = jnp.clip(kind, 0, 3)
    is_buy = side > 0
    is_add = k == MSG_ADD
    is_cancel = k == MSG_CANCEL
    matchable = is_add | (k == MSG_MARKET)

    # taker match against the opposite side
    ask_take = jnp.where(matchable & is_buy, qty, 0)
    ask_limit = jnp.where(is_add, price, PRICE_CAP)
    (ap, aq, ao), s_a = _match_half(ap, aq, ao, ask_take, ask_limit, True)
    bid_take = jnp.where(matchable & ~is_buy, qty, 0)
    bid_limit = jnp.where(is_add, price, 0)
    (bp, bq, bo), s_b = _match_half(bp, bq, bo, bid_take, bid_limit, False)

    # rest an ADD's unmatched remainder on its own side
    bid_rest = jnp.where(is_add & is_buy, qty - s_a[0], 0)
    (bp, bq, bo), rest_b = _rest_half(bp, bq, bo, price, bid_rest, oid)
    ask_rest = jnp.where(is_add & ~is_buy, qty - s_b[0], 0)
    (ap, aq, ao), rest_a = _rest_half(ap, aq, ao, price, ask_rest, oid)

    # cancel by (side, oid); target 0 hits nothing
    (bp, bq, bo), rm_b = _cancel_half(
        bp, bq, bo, jnp.where(is_cancel & is_buy, oid, 0)
    )
    (ap, aq, ao), rm_a = _cancel_half(
        ap, aq, ao, jnp.where(is_cancel & ~is_buy, oid, 0)
    )

    rec = FillRecord(
        filled_qty=s_a[0] + s_b[0],
        filled_value=s_a[1] + s_b[1],
        fill_events=s_a[2] + s_b[2],
        agent_qty=s_a[3] + s_b[3],
        agent_value=s_a[4] + s_b[4],
        price_min=jnp.minimum(s_a[5], s_b[5]),
        price_max=jnp.maximum(s_a[6], s_b[6]),
        rested_qty=rest_b + rest_a,
        cancelled_qty=rm_b + rm_a,
    )
    return (bp, bq, bo, ap, aq, ao), rec


def _to_lanes(book: BookState):
    """(..., D) / (..., D, Q) book arrays with books LEADING ->
    the lane layout: price (D, L), qty/oid Q-tuples of (D, L)."""
    out = []
    for name, x in zip(BookState._fields, book):
        if name.endswith("price"):
            out.append(jnp.moveaxis(x, 0, -1))                  # (D, L)
        else:
            x = jnp.moveaxis(x, 0, -1)                          # (D, Q, L)
            out.append(tuple(x[:, j] for j in range(x.shape[1])))
    return tuple(out)


def _from_lanes(halves) -> BookState:
    out = []
    for x in halves:
        if isinstance(x, tuple):
            x = jnp.stack(x, axis=1)                            # (D, Q, L)
        out.append(jnp.moveaxis(x, -1, 0))
    return BookState(*out)


def process_stream_dense(book: BookState, msgs: Messages):
    """XLA twin of the kernel body (same dense math, no pallas) — the
    parity tests use it to separate ranked-math bugs from pallas
    lowering bugs.  Not a dispatch target.  One book: L = 1."""

    def step(halves, m):
        halves, rec = _process_message_dense(
            halves, tuple(x.reshape(1, 1) for x in m)
        )
        return halves, FillRecord(*(x.reshape(()) for x in rec))

    halves0 = _to_lanes(BookState(*(jnp.asarray(x)[None] for x in book)))
    halves, fills = jax.lax.scan(step, halves0, tuple(msgs))
    return BookState(*(x[0] for x in _from_lanes(halves))), fills


# ---------------------------------------------------------------------------
# pallas dispatch: 128 books per program (one per lane), fori_loop over
# the stream, book state carried in vregs/VMEM across the whole stream
# ---------------------------------------------------------------------------
def _stream_kernel(bp_ref, bq_ref, bo_ref, ap_ref, aq_ref, ao_ref,
                   k_ref, s_ref, p_ref, q_ref, o_ref,
                   obp_ref, obq_ref, obo_ref, oap_ref, oaq_ref, oao_ref,
                   of_ref):
    slots = bq_ref.shape[0]
    n_msgs = k_ref.shape[0]

    def slab(ref):
        return tuple(ref[j] for j in range(slots))

    halves = (bp_ref[...], slab(bq_ref), slab(bo_ref),
              ap_ref[...], slab(aq_ref), slab(ao_ref))
    stream = (k_ref, s_ref, p_ref, q_ref, o_ref)

    def body(m, halves):
        msg = tuple(ref[pl.ds(m, 1), :] for ref in stream)       # (1, L)
        halves, rec = _process_message_dense(halves, msg)
        for c, col in enumerate(rec):
            of_ref[c, pl.ds(m, 1), :] = col
        return halves

    halves = jax.lax.fori_loop(0, n_msgs, body, halves)
    for ref, x in zip((obp_ref, obq_ref, obo_ref, oap_ref, oaq_ref,
                       oao_ref), halves):
        if isinstance(x, tuple):
            for j, face in enumerate(x):
                ref[j] = face
        else:
            ref[...] = x


@functools.lru_cache(maxsize=None)
def _make_stream(depth: int, slots: int, n_msgs: int, interpret: bool):
    from jax.custom_batching import custom_vmap

    lvl = pl.BlockSpec((depth, _LANES), lambda i: (0, i))
    slab = pl.BlockSpec((slots, depth, _LANES), lambda i: (0, 0, i))
    msg = pl.BlockSpec((n_msgs, _LANES), lambda i: (0, i))
    fill = pl.BlockSpec((_FILL_COLS, n_msgs, _LANES), lambda i: (0, 0, i))

    def batched(bp, bq, bo, ap, aq, ao, k, s, p, q, o):
        """Books-leading arrays in and out; the lane layout (books
        last, zero-padded to whole 128-lane programs — an empty book
        fed NOOPs stays empty, and the tail is sliced away) is internal."""
        b = bp.shape[0]
        lanes = -(-b // _LANES) * _LANES

        def lanes_last(x):                   # (B, ...) -> (..., lanes)
            x = jnp.pad(x, ((0, lanes - b),) + ((0, 0),) * (x.ndim - 1))
            # slabs (B, D, Q) -> (Q, D, B); rows (B, N) -> (N, B)
            return x.T

        out = pl.pallas_call(
            _stream_kernel,
            grid=(lanes // _LANES,),
            in_specs=[lvl, slab, slab, lvl, slab, slab,
                      msg, msg, msg, msg, msg],
            out_specs=[lvl, slab, slab, lvl, slab, slab, fill],
            out_shape=[
                jax.ShapeDtypeStruct((depth, lanes), jnp.int32),
                jax.ShapeDtypeStruct((slots, depth, lanes), jnp.int32),
                jax.ShapeDtypeStruct((slots, depth, lanes), jnp.int32),
                jax.ShapeDtypeStruct((depth, lanes), jnp.int32),
                jax.ShapeDtypeStruct((slots, depth, lanes), jnp.int32),
                jax.ShapeDtypeStruct((slots, depth, lanes), jnp.int32),
                jax.ShapeDtypeStruct((_FILL_COLS, n_msgs, lanes), jnp.int32),
            ],
            interpret=interpret,
        )(*(lanes_last(x) for x in (bp, bq, bo, ap, aq, ao, k, s, p, q, o)))
        # back to books-leading: (D, B)->(B, D), (Q, D, B)->(B, D, Q),
        # fills (C, M, B)->(B, M, C)
        return tuple(y.T[:b] for y in out)

    @custom_vmap
    def one(bp, bq, bo, ap, aq, ao, k, s, p, q, o):
        out = batched(*(x[None] for x in (bp, bq, bo, ap, aq, ao,
                                          k, s, p, q, o)))
        return tuple(y[0] for y in out)

    @one.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = tuple(
            x if bat else jnp.broadcast_to(x[None], (axis_size, *x.shape))
            for x, bat in zip(args, in_batched)
        )
        return tuple(batched(*args)), (True,) * 7

    return one


def fused_process_stream(
    book: BookState, msgs: Messages, *, interpret: bool | None = None,
):
    """``book.process_stream`` as one pallas program per 128 books: the
    books live in VMEM across the whole stream and every message is
    matched with the sort-free dense primitives.  Exact int32 parity
    with the argsort engine (tests/test_lob_match_kernel.py).  Composes
    with the trainers' per-env ``vmap`` via custom_vmap (batch -> lanes
    -> grid)."""
    interpret = resolve_interpret(interpret)
    depth = int(book.bid_qty.shape[-2])
    slots = int(book.bid_qty.shape[-1])
    n_msgs = int(msgs.kind.shape[-1])
    one = _make_stream(depth, slots, n_msgs, bool(interpret))
    arrays = tuple(
        jnp.asarray(x, jnp.int32) for x in (*book, *msgs)
    )
    out = one(*arrays)
    new_book = BookState(*out[:6])
    fills = out[6]
    rec = FillRecord(*(fills[..., i] for i in range(_FILL_COLS)))
    return new_book, rec
