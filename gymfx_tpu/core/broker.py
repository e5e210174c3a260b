"""Branch-free broker ledger kernel.

Replaces backtrader's BackBroker + order matching (the engine side of
reference app/bt_bridge.py:136-248, broker config
broker_plugins/default_broker.py:35-53) with pure functions over the
``EnvState`` ledger fields, composable under ``jit``/``vmap``/``scan``.

Execution model (matching backtrader's default, no cheat-on-open):
  * market orders created at bar t (the strategy acts on bar t's close)
    execute at bar t+1's OPEN;
  * percent slippage is applied adversely by fill direction
    (buy: open*(1+slip); sell: open*(1-slip));
  * commission = commission_rate * fill_price * |units| per executed
    order; a long<->short flip is close+open = two orders, equivalent
    to commission on |delta| at one fill price;
  * equity = cash + position * close, marked at every bar close.

Bracket (SL/TP) semantics: armed when the parent entry fills; evaluated
against each bar's H/L while the position is open; collision policies
``worst_case`` (SL wins when both touched — reference
simulation_engines/contracts.py:100, bakeoff fixture semantics
bakeoff.py:116-163), ``ohlc`` (O->H->L->C path order) and ``adaptive``
(treated as worst_case).  Deliberate divergence from the reference
backtrader path: closing a bracketed position cancels its children
(backtrader leaves orphaned child orders alive — a latent footgun the
scan kernel does not reproduce).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from gymfx_tpu.core.types import (
    EXEC_DIAG_INDEX,
    EnvConfig,
    EnvParams,
    EnvState,
)


def bump_exec_diag(diag, key: str, amount):
    """``diag[EXEC_DIAG_INDEX[key]] += amount`` as a dense one-hot add
    over the counter axis (axis 0) instead of ``.at[].add``: integer
    adds are exact, so the result is the same, and a one-hot add is a
    form Mosaic lowers — ``fill_pending`` runs inside the fused
    env-dynamics kernel (ops/env_dynamics.py), where a scatter-add does
    not.  ``amount`` broadcasts against ``diag.shape[1:]``."""
    rows = jax.lax.broadcasted_iota(jnp.int32, diag.shape, 0)
    hot = rows == EXEC_DIAG_INDEX[key]
    return diag + jnp.where(hot, amount.astype(jnp.int32), 0)


def pick_mask(pred, a, b):
    """``jnp.where(pred, a, b)`` for boolean ``a``/``b`` in logic form:
    the same truth table, and a form Mosaic lowers (it has no select on
    mask vectors) — the bracket chain runs inside the fused env-dynamics
    kernel too."""
    return (pred & a) | (~pred & b)


def quantize(x, tick):
    """Round ``x`` to the nearest multiple of ``tick``; identity when
    tick == 0 (the venue-quantization-off sentinel).  Round-half-even,
    matching the replay venue's ``make_price``/``make_qty`` (Python
    ``round``) so both engines land on the same grid.

    The arithmetic runs in float64 when x64 is enabled (bit-parity with
    the replay venue's double rounding).  In pure-f32 mode the ratio
    ``x/tick`` (~1e5 for FX ticks) keeps only ~7 fractional bits, so a
    value within ~0.01 tick of a midpoint can round to the adjacent
    tick vs the f64 path — the crosscheck bound carries a documented
    midpoint-flip slack for exactly this (simulation/crosscheck.py)."""
    x = jnp.asarray(x)
    if jax.config.jax_enable_x64:
        xi, ti = x.astype(jnp.float64), jnp.asarray(tick, jnp.float64)
        safe = jnp.where(ti > 0, ti, 1.0)
        return jnp.where(ti > 0, jnp.round(xi / safe) * safe, xi).astype(x.dtype)
    safe = jnp.where(tick > 0, tick, 1.0)
    return jnp.where(tick > 0, jnp.round(x / safe) * safe, x)


def snap_in_bar(price, low, high, tick):
    """Clip ``price`` into the bar's [low, high], then snap to the
    nearest IN-BAR venue tick, so ``apply_fill``'s round-half-even
    re-quantization is an identity and slip_match's in-range guarantee
    survives venue quantization (ADVICE r4).  Each one-tick correction
    only fires when it LANDS in-bar: a bar narrower than one tick
    (off-grid H/L, a data/venue inconsistency) keeps the nearest tick —
    the best on-grid price that exists — instead of oscillating.
    Identity when tick == 0 (quantization off)."""
    p = jnp.clip(price, low, high)
    q = quantize(p, tick)
    t = jnp.asarray(tick, q.dtype)
    down, up = q - t, q + t
    q = jnp.where((q > high) & (down >= low), down, q)
    q = jnp.where((q < low) & (up <= high), up, q)
    return q


def opening_units(pos, target):
    """Units newly opened by moving ``pos`` -> ``target``: the size
    increase when flat/adding, the whole new position on a flip.
    (Single source for preflight and fill decomposition semantics.)"""
    same_sign = pos * target > 0
    opening = jnp.maximum(jnp.abs(target) - jnp.abs(pos), 0.0)
    return jnp.where(
        (~same_sign) & (target != 0) & (pos != 0), jnp.abs(target), opening
    )


def maintenance_margin(pos, price, params: EnvParams, margin_model: str):
    """Maintenance requirement of the open position, in quote currency:
    |pos| * price * margin_maint, divided by leverage under the
    leveraged model — the same model split as the init-margin preflight
    (reference margin models, simulation_engines/nautilus_adapter.py:397-427)."""
    m = jnp.abs(pos) * price * params.margin_maint
    if margin_model == "leveraged":
        m = m / jnp.maximum(params.leverage, 1e-12)
    return m


def margin_closeout_percent(state: EnvState, price, params: EnvParams,
                            margin_model: str, cap: float = 100.0):
    """How close the account is to liquidation: maintenance margin over
    equity — 0 flat, 1.0 at the closeout boundary, capped when equity is
    non-positive.  This is the REAL-ledger value behind the
    ``margin_closeout_percent`` obs field (the reference publishes it
    from its margin account when one exists, app/env.py:615-623)."""
    maint = maintenance_margin(state.pos, price, params, margin_model)
    eq = params.initial_cash + state.equity_delta
    pct = jnp.where(eq > 0, maint / jnp.maximum(eq, 1e-30), cap)
    pct = jnp.where(state.pos == 0, 0.0, pct)
    return jnp.clip(pct, 0.0, cap)


def realized_balance(state: EnvState, params: EnvParams):
    """Realized-PnL account balance (initial + realized - commissions):
    cash plus the open position's entry notional — the same measure the
    replay engine's margin preflight compares against
    (simulation/replay.py balance semantics)."""
    return params.initial_cash + state.cash_delta + state.pos * state.entry_price


def apply_fill(
    state: EnvState, fill_price, target_units, params: EnvParams
) -> EnvState:
    """Move the position to ``target_units`` at ``fill_price`` (pre-slippage).

    No-op when ``target_units == pos``.  Handles open/add/reduce/close/
    flip with avg-entry-price tracking, commission accrual and
    closed-trade statistics.
    """
    d = state.pos.dtype
    pos = state.pos
    target = jnp.asarray(target_units, dtype=d)
    delta = target - pos
    direction = jnp.sign(delta)
    # venue quantization (opt-in): the book holds prices at the
    # instrument's tick, so the post-slippage fill price is quantized —
    # the replay venue's make_price on bid/ask (simulation/replay.py
    # market_price)
    fill = quantize(fill_price * (1.0 + params.slippage * direction),
                    params.price_tick)

    abs_pos = jnp.abs(pos)
    abs_target = jnp.abs(target)
    same_sign = pos * target > 0
    # units closed out of the existing position by this fill
    closed = jnp.where(
        same_sign,
        jnp.maximum(abs_pos - abs_target, 0.0),
        abs_pos,
    )
    closed = jnp.where(delta == 0, 0.0, closed)
    opened = jnp.abs(delta) - closed

    realized = closed * (fill - state.entry_price) * jnp.sign(pos)
    commission = params.commission * fill * jnp.abs(delta)
    comm_close = params.commission * fill * closed
    comm_open = commission - comm_close

    cash_delta = state.cash_delta - delta * fill - commission

    # average entry price of the resulting position
    new_abs = jnp.abs(target)
    adding = same_sign & (abs_target > abs_pos)
    flipping = (~same_sign) & (target != 0) & (pos != 0)
    opening = (pos == 0) & (target != 0)
    entry = jnp.where(
        adding,
        (state.entry_price * abs_pos + fill * (new_abs - abs_pos)) / jnp.maximum(new_abs, 1e-30),
        state.entry_price,
    )
    entry = jnp.where(flipping | opening, fill, entry)
    entry = jnp.where(target == 0, 0.0, entry)

    # closed-trade bookkeeping: a trade closes when the old position is
    # fully exited (to flat or by flip) — reference counts on
    # trade.isclosed (app/bt_bridge.py:132-134)
    trade_closed = (pos != 0) & ((target == 0) | flipping)
    trade_net = realized - (state.open_trade_commission + comm_close)
    trade_count = state.trade_count + trade_closed.astype(jnp.int32)
    trade_pnl_sum = state.trade_pnl_sum + jnp.where(trade_closed, trade_net, 0.0)
    trade_pnl_sumsq = state.trade_pnl_sumsq + jnp.where(trade_closed, trade_net**2, 0.0)
    trades_won = state.trades_won + (trade_closed & (trade_net > 0)).astype(jnp.int32)
    trades_lost = state.trades_lost + (trade_closed & (trade_net < 0)).astype(jnp.int32)
    open_trade_commission = jnp.where(
        trade_closed, comm_open, state.open_trade_commission + comm_open
    )
    open_trade_commission = jnp.where(target == 0, 0.0, open_trade_commission)

    return state._replace(
        pos=target,
        entry_price=entry,
        cash_delta=cash_delta,
        commission_paid=state.commission_paid + commission,
        last_trade_cost=state.last_trade_cost + commission,
        trade_count=trade_count,
        trade_pnl_sum=trade_pnl_sum,
        trade_pnl_sumsq=trade_pnl_sumsq,
        trades_won=trades_won,
        trades_lost=trades_lost,
        open_trade_commission=open_trade_commission,
    )


def fill_pending(
    state: EnvState, open_price, params: EnvParams,
    cfg: EnvConfig = None, high=None, low=None,
) -> EnvState:
    """Execute the pending market order at the new bar's open.

    Venue quantization (opt-in, zero-sentinel params): the order DELTA
    is rounded to the instrument's size step and orders below
    min_quantity are denied — the replay venue's make_qty/min_quantity
    rule (simulation/replay.py process_action; reference RiskEngine,
    nautilus_adapter.py:190).  Denials apply to closing orders too,
    exactly like the replay engine.

    Per-fill-type slippage switches (reference backtrader
    set_slippage_perc, broker_plugins/default_broker.py:52): with
    ``cfg.slip_open`` off, fills at the open take no slippage; with
    ``cfg.slip_match`` on (and ``high``/``low`` given), the slipped
    price is capped into the bar's range.  The default flags take the
    untouched code path — bit-identical to the pre-toggle kernel.
    """
    raw_target = jnp.where(state.pending_active, state.pending_target, state.pos)
    delta = raw_target - state.pos
    qty = quantize(jnp.abs(delta), params.size_step)
    # A venue-forced liquidation (maintenance-margin closeout) bypasses the
    # size rules entirely: it fills the exact open position, un-quantized
    # and below min_quantity if need be — the replay venue's bypass
    # (simulation/replay.py check_margin_closeout: "a venue never strands
    # a liquidation on a size rule").  Without this a position left below
    # min_qty by partial reduces would be permanently unliquidatable.
    forced = state.pending_active & state.pending_forced
    qty = jnp.where(forced, jnp.abs(delta), qty)
    denied = (
        state.pending_active
        & ~forced
        & (delta != 0)
        & ((qty < params.min_qty) | ((params.size_step > 0) & (qty <= 0)))
    )
    target = jnp.where(denied, state.pos, state.pos + jnp.sign(delta) * qty)
    state = state._replace(
        exec_diag=bump_exec_diag(
            state.exec_diag, "order_denied_min_quantity", denied
        )
    )
    fill_price = open_price
    slip_open = cfg.slip_open if cfg is not None else True
    slip_match = (cfg.slip_match if cfg is not None else False) and high is not None
    if (not slip_open) or slip_match:
        # pre-adjust so apply_fill's own slippage lands on the desired
        # final price (the same neutralization trick as the TP path)
        direction = jnp.sign(target - state.pos)
        final = open_price * (
            1.0 + params.slippage * (1.0 if slip_open else 0.0) * direction
        )
        if slip_match:
            final = snap_in_bar(final, low, high, params.price_tick)
        denom = 1.0 + params.slippage * direction
        fill_price = final / jnp.where(denom == 0, 1.0, denom)
    new_state = apply_fill(state, fill_price, target, params)
    # Re-arm brackets only when the fill actually OPENED units (fresh
    # entry or flip) — a fill that merely reduces an existing bracketed
    # position must not overwrite its live brackets with the reduce
    # order's (zero) SL/TP.
    entered = (
        state.pending_active
        & (new_state.pos != 0)
        & (opening_units(state.pos, target) > 0)
    )
    # bracket levels rest on the venue book -> quantized at arming (the
    # replay's make_price on sl/tp; identity when quantization is off)
    bracket_sl = jnp.where(
        entered, quantize(state.pending_sl, params.price_tick), state.bracket_sl
    )
    bracket_tp = jnp.where(
        entered, quantize(state.pending_tp, params.price_tick), state.bracket_tp
    )
    flat = new_state.pos == 0
    return new_state._replace(
        pending_active=jnp.zeros_like(state.pending_active),
        pending_target=jnp.zeros_like(state.pending_target),
        pending_sl=jnp.zeros_like(state.pending_sl),
        pending_tp=jnp.zeros_like(state.pending_tp),
        pending_forced=jnp.zeros_like(state.pending_forced),
        bracket_sl=jnp.where(flat, 0.0, bracket_sl),
        bracket_tp=jnp.where(flat, 0.0, bracket_tp),
    )


def check_brackets(
    state: EnvState, open_price, high, low, cfg: EnvConfig, params: EnvParams
) -> EnvState:
    """Resolve SL/TP exits intrabar against the bar's H/L."""
    pos = state.pos
    has_pos = pos != 0
    long = pos > 0
    sl = state.bracket_sl
    tp = state.bracket_tp
    has_sl = sl > 0
    has_tp = tp > 0

    # trigger + raw fill price per side (stop orders gap-fill at open).
    # The take-profit (a limit order) honors the profile's
    # limit_fill_policy (contracts.py _LIMIT_FILL_POLICIES; reference
    # simulation_engines/contracts.py:101):
    #   conservative  price must trade THROUGH the limit (strict
    #                 inequality — an exact touch does not fill, modeling
    #                 queue position); fills at the limit price exactly;
    #   touch         an exact touch fills, at the limit price exactly;
    #   cross         an exact touch fills, and a bar that gaps open
    #                 beyond the limit fills at the open (price
    #                 improvement) — the scan engine's no-profile default.
    sl_trig = has_pos & has_sl & pick_mask(long, low <= sl, high >= sl)
    strict = cfg.limit_fill_policy == "conservative"
    if strict:
        tp_trig = has_pos & has_tp & pick_mask(long, high > tp, low < tp)
    else:
        tp_trig = has_pos & has_tp & pick_mask(long, high >= tp, low <= tp)
    sl_fill = jnp.where(
        long,
        jnp.where(open_price <= sl, open_price, sl),
        jnp.where(open_price >= sl, open_price, sl),
    )
    if cfg.limit_fill_policy == "cross":
        tp_fill = jnp.where(
            long,
            jnp.where(open_price >= tp, open_price, tp),
            jnp.where(open_price <= tp, open_price, tp),
        )
    else:  # conservative / touch: a limit never fills better than its price
        tp_fill = tp

    if cfg.intrabar_collision_policy == "ohlc":
        # Walk the O->H->L->C path.  A bar that opens through either
        # bracket fills it at the open (gap_sl and gap_tp are mutually
        # exclusive: SL and TP sit on opposite sides of the entry).
        # With no gap, longs reach TP on the O->H leg before SL on H->L;
        # shorts reach SL (above) on the O->H leg before TP on H->L.
        gap_sl = has_pos & has_sl & pick_mask(long, open_price <= sl, open_price >= sl)
        if strict:
            gap_tp = has_pos & has_tp & pick_mask(
                long, open_price > tp, open_price < tp
            )
        else:
            gap_tp = has_pos & has_tp & pick_mask(
                long, open_price >= tp, open_price <= tp
            )
        exit_sl = gap_sl | (
            sl_trig & ~gap_tp & pick_mask(long, ~tp_trig, jnp.ones_like(gap_sl))
        )
        exit_tp = (gap_tp | tp_trig) & ~exit_sl
    else:  # worst_case / adaptive
        exit_sl = sl_trig
        exit_tp = tp_trig & ~sl_trig

    exiting = exit_sl | exit_tp
    # SL exits suffer adverse slippage (stop -> market); TP exits fill at
    # the limit price exactly (a limit cannot fill worse than its price)
    # unless cfg.slip_limit re-enables slippage on them (capped at the
    # limit).  cfg.slip_open / cfg.slip_match adjust gap and intrabar
    # fills per the reference broker's set_slippage_perc switches; the
    # default flags take the original code path bit-for-bit.
    exit_dir = -jnp.sign(pos)  # sell to exit long, buy to exit short
    denom = 1.0 + params.slippage * exit_dir
    safe_denom = jnp.where(denom == 0, 1.0, denom)
    if cfg.slip_open and not cfg.slip_match:
        sl_adj = sl_fill  # apply_fill slips it (historical path)
    else:
        sl_gap = has_pos & has_sl & pick_mask(
            long, open_price <= sl, open_price >= sl
        )
        # gap SLs execute at the open (slip_open gates them); intrabar
        # stop fills always slip
        sl_scale = jnp.where(sl_gap, 1.0 if cfg.slip_open else 0.0, 1.0)
        sl_final = sl_fill * (1.0 + params.slippage * sl_scale * exit_dir)
        if cfg.slip_match:
            sl_final = snap_in_bar(sl_final, low, high, params.price_tick)
        sl_adj = sl_final / safe_denom
    if cfg.slip_limit:
        tp_final = tp_fill * (1.0 + params.slippage * exit_dir)
        if cfg.slip_match:
            tp_final = snap_in_bar(tp_final, low, high, params.price_tick)
        # a limit never fills worse than its price (cap applied last)
        tp_final = jnp.where(
            long, jnp.maximum(tp_final, tp), jnp.minimum(tp_final, tp)
        )
        tp_adj = tp_final / safe_denom
    else:
        tp_adj = tp_fill / safe_denom  # neutralize: fill at the limit exactly
    adj_price = jnp.where(exit_sl, sl_adj, tp_adj)

    target = jnp.where(exiting, 0.0, pos)
    new_state = apply_fill(state, jnp.where(exiting, adj_price, open_price), target, params)
    return new_state._replace(
        bracket_sl=jnp.where(exiting, 0.0, state.bracket_sl),
        bracket_tp=jnp.where(exiting, 0.0, state.bracket_tp),
    )


def mark_to_market(state: EnvState, close_price, params: EnvParams) -> EnvState:
    """Mark equity at the bar close; update drawdown tracking."""
    equity_delta = state.cash_delta + state.pos * close_price
    peak = jnp.maximum(state.peak_equity_delta, equity_delta)
    money_down = peak - equity_delta
    peak_equity = params.initial_cash + peak
    pct_down = jnp.where(peak_equity > 0, money_down / peak_equity * 100.0, 0.0)
    return state._replace(
        prev_equity_delta=state.equity_delta,
        equity_delta=equity_delta,
        peak_equity_delta=peak,
        max_drawdown_money=jnp.maximum(state.max_drawdown_money, money_down),
        max_drawdown_pct=jnp.maximum(state.max_drawdown_pct, pct_down),
    )


def equity(state: EnvState, params: EnvParams):
    return params.initial_cash + state.equity_delta


def prev_equity(state: EnvState, params: EnvParams):
    return params.initial_cash + state.prev_equity_delta
