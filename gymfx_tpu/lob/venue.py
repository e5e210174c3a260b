"""LOB execution venue: one bar's agent execution through the book.

``execute_bar`` replaces the bar engine's advance steps 1 and 2
(broker.fill_pending + broker.check_brackets, core/env.py) when
``cfg.venue == "lob"``.  Semantics per advancing bar:

  1. a fresh book is seeded at the bar open (``lob_seed_levels`` levels
     per side, flow.seed_messages) — per-bar books keep the state
     static-shape and scan-free across bars while the seeded depth
     models persistent liquidity;
  2. the pending order executes as a market walk: ``lots =
     round(|delta| / lot_units)`` lots consume the book best-price
     first; the unfilled remainder is priced at the worst touched level
     (the depth-derived slippage the bar engine cannot express), or at
     the bar open when the book gave nothing.  Sub-lot orders are
     DENIED (the venue's min-quantity rule, same diagnostics counter as
     the bar engine's size rules); a venue-forced liquidation
     (margin closeout) always trades at least one lot and moves the
     ledger to its exact target — a venue never strands a liquidation;
  3. the take-profit rests IN the book as an agent limit order
     (owner ``AGENT_OID``): it earns queue position behind the seeded
     depth at its level, fills only when flow takers reach it, and a
     bar that gaps open through it fills the marketable part
     immediately at maker prices (the bar engine's ``cross`` gap
     semantics, now emergent from matching);
  4. the stop-loss is a stop: tracked off-book and triggered by PRINTS
     — the first flow fill at or through the stop fires a market exit
     of the remaining lots (and cancels the resting TP); the unfilled
     remainder is priced at the stop level;
  5. all agent executions of the bar aggregate into at most two ledger
     fills (entry at open, exit at the lots-weighted vwap) through
     ``broker.apply_fill`` — exact, because realized PnL and commission
     are linear in fill price at fixed quantities.

The pure-Python twin of this function is ``oracle.OracleVenue``;
``simulation/crosscheck.crosscheck_lob_episode`` reconciles the two.

Honor-or-reject (``validate_lob_venue``, bound at Environment
construction): config knobs whose semantics the LOB venue replaces —
fractional slippage, venue quantization, execution cost profiles,
explicit limit-fill/collision policies — and kernels it cannot honor
yet (the calendar force-close session filter) fail loudly instead of
being silently degraded.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from gymfx_tpu.core import broker
from gymfx_tpu.core.types import EXEC_DIAG_INDEX, EnvConfig, EnvParams, EnvState
from gymfx_tpu.ops.dispatch import kernel_interpret

from .book import (
    AGENT_OID,
    BookState,
    FillRecord,
    add_limit,
    cancel,
    empty_book,
    match_market,
    process_message,
    process_stream,
)
from .flow import bar_key, bar_messages, price_to_ticks, seed_messages
from .scenarios import scenario_flow_params


def lot_size(cfg: EnvConfig, params: EnvParams):
    """Units per lot: the static config override, else position_size —
    so default strategies (target = ±position_size) trade one lot."""
    if cfg.lob_lot_units > 0:
        return jnp.asarray(cfg.lob_lot_units, params.position_size.dtype)
    return params.position_size


def to_lots(units, lot_units):
    """|units| -> integer lots (round-half-even, matching the oracle's
    Python round)."""
    return jnp.round(jnp.abs(units) / lot_units).astype(jnp.int32)


def bracket_ticks(price, tick):
    """Bracket price -> tick grid (0 stays 0 = disarmed)."""
    return jnp.round(price / tick).astype(jnp.int32)


def _vwap_price(value, lots, tick, dtype):
    """Integer (tick*lots) fill value -> per-unit float price."""
    lots_f = jnp.maximum(lots, 1).astype(dtype)
    return value.astype(dtype) / lots_f * jnp.asarray(tick, dtype)


def _walk_with_backstop(book: BookState, is_buy, lots, backstop_ticks):
    """Market-walk ``lots`` against the book; the unfilled remainder is
    priced at the worst touched level (else ``backstop_ticks``).
    Returns (book, total_value_ticklots, worst_touched)."""
    book, fill = match_market(book, is_buy, lots)
    worst = jnp.where(
        fill.filled_qty > 0,
        jnp.where(is_buy, fill.price_max, fill.price_min),
        backstop_ticks,
    )
    value = fill.filled_value + (lots - fill.filled_qty) * worst
    return book, value, worst


def execute_bar(
    state: EnvState, o, h, l, c, t_global, cfg: EnvConfig, params: EnvParams,
    scen_flags=None,
) -> EnvState:
    """One advancing bar through the LOB venue (replaces fill_pending +
    check_brackets; the caller gates with its ``advance`` select).

    ``scen_flags`` (feed=scengen only): the bar's scenario bitmask —
    the static FlowParams preset is blended per bar so the flow thins
    in droughts and bursts through crash windows
    (scenarios.flow_params_from_regime).
    """
    d = state.pos.dtype
    tick = cfg.lob_tick_size
    fp = scenario_flow_params(cfg.lob_scenario)
    if scen_flags is not None:
        from .scenarios import flow_params_from_regime

        fp = flow_params_from_regime(
            fp, scen_flags, cfg.lob_messages_per_bar
        )

    o_t = price_to_ticks(o, tick)
    c_t = price_to_ticks(c, tick)
    h_t = jnp.maximum(price_to_ticks(h, tick), jnp.maximum(o_t, c_t))
    l_t = jnp.minimum(price_to_ticks(l, tick), jnp.minimum(o_t, c_t))

    # fresh per-bar book, seeded with deterministic baseline depth;
    # lob_match_kernel routes the seed stream through the sort-free
    # pallas matcher (ops/lob_match.py) — exact int32 parity with the
    # argsort engine; off|on|interpret resolves in ops/dispatch
    book = empty_book(cfg.lob_depth_levels, cfg.lob_queue_slots)
    seed = seed_messages(o_t, cfg.lob_seed_levels, fp)
    match_interpret = kernel_interpret(cfg.lob_match_kernel)
    if match_interpret is None:
        book, _ = process_stream(book, seed)
    else:
        from gymfx_tpu.ops import lob_match

        book, _ = lob_match.fused_process_stream(
            book, seed, interpret=match_interpret
        )

    lot_units = lot_size(cfg, params)

    # ---- 1. pending order: market walk at the bar open -------------------
    raw_target = jnp.where(state.pending_active, state.pending_target, state.pos)
    delta = raw_target - state.pos
    lots_raw = to_lots(delta, lot_units)
    forced = state.pending_active & state.pending_forced
    # a forced liquidation always trades (>= 1 lot for pricing) and the
    # ledger lands exactly on its target — same bypass as fill_pending
    lots = jnp.where(forced & (delta != 0), jnp.maximum(lots_raw, 1), lots_raw)
    denied = state.pending_active & ~forced & (delta != 0) & (lots < 1)
    exec_lots = jnp.where(state.pending_active & ~denied, lots, 0)
    is_buy = delta > 0
    book, open_value, _ = _walk_with_backstop(book, is_buy, exec_lots, o_t)
    open_price = _vwap_price(open_value, exec_lots, tick, d)

    signed_lots = jnp.sign(delta) * exec_lots.astype(d) * lot_units
    ledger_target = jnp.where(denied, state.pos, state.pos + signed_lots)
    ledger_target = jnp.where(forced, raw_target, ledger_target)

    state = state._replace(
        exec_diag=state.exec_diag.at[
            EXEC_DIAG_INDEX["order_denied_min_quantity"]
        ].add(denied.astype(jnp.int32))
    )
    st = broker.apply_fill(
        state, jnp.where(exec_lots > 0, open_price, o), ledger_target, params
    )

    # brackets arm when the fill OPENED units (entry/flip), quantized to
    # the venue tick grid (stored as ticks * tick so the oracle recovers
    # the integer exactly); a reduce keeps the live brackets
    entered = (
        state.pending_active
        & (st.pos != 0)
        & (broker.opening_units(state.pos, ledger_target) > 0)
    )
    t = jnp.asarray(tick, d)
    sl_armed = bracket_ticks(state.pending_sl, tick).astype(d) * t
    tp_armed = bracket_ticks(state.pending_tp, tick).astype(d) * t
    flat = st.pos == 0
    st = st._replace(
        pending_active=jnp.zeros_like(state.pending_active),
        pending_target=jnp.zeros_like(state.pending_target),
        pending_sl=jnp.zeros_like(state.pending_sl),
        pending_tp=jnp.zeros_like(state.pending_tp),
        pending_forced=jnp.zeros_like(state.pending_forced),
        bracket_sl=jnp.where(flat, 0.0, jnp.where(entered, sl_armed, st.bracket_sl)),
        bracket_tp=jnp.where(flat, 0.0, jnp.where(entered, tp_armed, st.bracket_tp)),
    )

    # ---- 2. intrabar: TP rests in the book, SL triggers on prints --------
    pos_lots = to_lots(st.pos, lot_units)
    long = st.pos > 0
    exit_is_buy = ~long  # exiting a short buys
    sl = bracket_ticks(st.bracket_sl, tick)
    tp = bracket_ticks(st.bracket_tp, tick)
    has_sl = (sl > 0) & (pos_lots > 0)
    has_tp = (tp > 0) & (pos_lots > 0)

    # a bar that gaps open through the stop exits at the open walk
    gap_sl = has_sl & jnp.where(long, o_t <= sl, o_t >= sl)
    gap_lots = jnp.where(gap_sl, pos_lots, 0)
    book, gap_value, _ = _walk_with_backstop(book, exit_is_buy, gap_lots, o_t)

    # rest the TP (skipped when the gap stop already flattened the bar);
    # its marketable part fills immediately at maker prices (gap cross)
    tp_rest = jnp.where(has_tp & ~gap_sl, pos_lots, 0)
    book, tp_fill0 = add_limit(
        book, exit_is_buy, jnp.maximum(tp, 1), tp_rest, AGENT_OID
    )

    rem0 = pos_lots - gap_lots - tp_fill0.filled_qty
    carry0 = (
        book,
        rem0,
        gap_sl,                                   # sl_fired
        tp_fill0.filled_qty, tp_fill0.filled_value,
        gap_lots, gap_value,
    )

    def flow_step(carry, msg):
        bk, rem, fired, tp_lots, tp_value, sl_lots, sl_value = carry
        bk, fill = process_message(bk, msg)
        # flow takers reaching our resting TP (maker fills)
        rem = rem - fill.agent_qty
        tp_lots = tp_lots + fill.agent_qty
        tp_value = tp_value + fill.agent_value
        # stop trigger: the first print at/through the stop level
        printed = jnp.where(
            long, fill.price_min <= sl, fill.price_max >= sl
        )
        trig = has_sl & ~fired & (rem > 0) & printed

        def fire(args):
            bk, rem = args
            bk, _ = cancel(bk, exit_is_buy, AGENT_OID)  # pull the TP
            return _walk_with_backstop(bk, exit_is_buy, rem, sl)

        bk, xvalue, _ = jax.lax.cond(
            trig, fire, lambda a: (a[0], jnp.int32(0), jnp.int32(0)),
            (bk, rem),
        )
        sl_lots = sl_lots + jnp.where(trig, rem, 0)
        sl_value = sl_value + jnp.where(trig, xvalue, 0)
        rem = jnp.where(trig, 0, rem)
        return (bk, rem, fired | trig, tp_lots, tp_value, sl_lots, sl_value), None

    flow = bar_messages(
        bar_key(cfg.lob_flow_seed, t_global),
        o_t, h_t, l_t, c_t, cfg.lob_messages_per_bar, fp,
    )
    carry, _ = jax.lax.scan(flow_step, carry0, tuple(flow))
    _, rem, sl_fired, tp_lots, tp_value, sl_lots, sl_value = carry

    # ---- 3. aggregate exit fill (lots-weighted vwap; exact: realized
    #         PnL and commission are linear in price at fixed lots) -------
    exit_lots = tp_lots + sl_lots
    exit_value = tp_value + sl_value
    full_exit = (exit_lots >= pos_lots) & (pos_lots > 0)
    exit_target = jnp.where(
        full_exit,
        jnp.zeros_like(st.pos),
        st.pos - jnp.sign(st.pos) * exit_lots.astype(d) * lot_units,
    )
    exit_price = _vwap_price(exit_value, exit_lots, tick, d)
    st = broker.apply_fill(
        st,
        jnp.where(exit_lots > 0, exit_price, o),
        jnp.where(exit_lots > 0, exit_target, st.pos),
        params,
    )
    # brackets survive a partial TP (re-rested with the remaining lots
    # next bar); a full exit or fired stop clears them
    now_flat = st.pos == 0
    return st._replace(
        bracket_sl=jnp.where(now_flat | sl_fired, 0.0, st.bracket_sl),
        bracket_tp=jnp.where(now_flat | sl_fired, 0.0, st.bracket_tp),
    )


def validate_lob_venue(cfg: EnvConfig, config: Dict[str, Any]) -> None:
    """Honor-or-reject at Environment binding time (the
    validate_profile_latency pattern, core/runtime.py): every config
    knob is either honored by the LOB venue or rejected loudly."""
    if cfg.venue != "lob":
        return
    problems = []
    if cfg.session_filter:
        problems.append(
            "session_filter=True: the calendar force-close strategy "
            "semantics are not implemented on the LOB venue yet"
        )
    if config.get("venue_quantization"):
        problems.append(
            "venue_quantization=True: the LOB venue quotes on its own "
            "lob_tick_size grid; the bar engine's tick/size-step "
            "quantization cannot be honored on top of it"
        )
    slippage = float(
        config.get("slippage_perc", config.get("slippage", 0.0)) or 0.0
    )
    if slippage != 0.0:
        problems.append(
            f"slippage={slippage}: the LOB venue derives slippage from "
            "book depth; fractional price slippage cannot be honored"
        )
    if config.get("execution_cost_profile"):
        problems.append(
            "execution_cost_profile: profiles drive spread/slippage "
            "displacement and fill policies the LOB venue replaces with "
            "book matching"
        )
    if str(config.get("limit_fill_policy", "cross")) != "cross":
        problems.append(
            f"limit_fill_policy={config['limit_fill_policy']!r}: the LOB "
            "take-profit is a resting limit order — touch/queue semantics "
            "come from matching, not a policy knob; only the default "
            "'cross' is honored"
        )
    if "intrabar_collision_policy" in config:
        problems.append(
            "intrabar_collision_policy: the LOB venue resolves SL/TP by "
            "actual print order along the flow path; collision policies "
            "are a bar-engine concept"
        )
    if problems:
        raise ValueError(
            "venue=lob cannot honor this configuration:\n  - "
            + "\n  - ".join(problems)
        )
