"""The functional environment: ``reset`` / ``step`` as pure JAX.

One ``step`` fuses what the reference spreads across two threads and a
per-bar Event handshake (reference app/env.py:279-328 on the main
thread, app/bt_bridge.py:136-248 on the cerebro thread):

  coerce action -> event-context overlay -> diagnostics ->
  [advance bar: fill pending at open, resolve brackets intrabar,
   apply strategy at close, mark equity] -> reward -> obs/info

Step/bar timing parity with the reference handshake:
  * ``reset`` yields the observation at bar_index=1 (first bar
    processed, warmup publish — reference bt_bridge.py:144-151);
  * the FIRST ``step`` applies its action on that same bar without
    advancing (the order fills at bar 2's open);
  * every subsequent step advances exactly one bar: the previous
    action's order fills at the new bar's open, brackets resolve
    against the new bar's H/L, the new action is applied at its close,
    equity is marked at that close;
  * a step taken when the final bar was already processed terminates
    the episode without advancing (reference cerebro stop() path).

Documented divergences from the reference (quirks not reproduced):
  * ``last_trade_cost`` reports the commissions actually paid during
    the step; the reference zeroes its accumulator after notification
    delivery and therefore always publishes 0.0 (bt_bridge.py:175,239-248);
  * on the terminal exhausted step the sharpe reward buffer is not
    cleared-and-repopulated (the reference's step-regression reset
    fires there, sharpe_reward.py:42-45); pnl/dd rewards match exactly.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from gymfx_tpu.core import broker, rewards, strategy
from gymfx_tpu.core.obs import build_info, build_obs
from gymfx_tpu.core.types import (
    ACTION_DIAG_INDEX,
    EXEC_DIAG_INDEX,
    EnvConfig,
    EnvParams,
    EnvState,
    initial_state,
)
from gymfx_tpu.data.feed import MarketData, read_bar
from gymfx_tpu.ops.dispatch import kernel_interpret
from gymfx_tpu.telemetry import scopes


def jit_reset(cfg, params, data):
    """Module-level jitted reset — cached across Environment instances
    (a per-instance jax.jit wrapper would recompile for every env)."""
    return _JIT_RESET(cfg, params, data)


def jit_step(cfg, params, data, state, action):
    """Module-level jitted step (see jit_reset)."""
    return _JIT_STEP(cfg, params, data, state, action)


def reset(
    cfg: EnvConfig, params: EnvParams, data: MarketData
) -> Tuple[EnvState, Dict[str, Any]]:
    """Start an episode; returns (state, obs) at bar_index=1."""
    return reset_at(cfg, params, data, 0)


def reset_at(
    cfg: EnvConfig, params: EnvParams, data: MarketData, t0
) -> Tuple[EnvState, Dict[str, Any]]:
    """Reset with the episode starting at bar row ``t0`` (traced).

    New capability for training diversity (the reference always starts
    at bar 1): rollout collectors draw random start offsets so an env
    batch covers the dataset instead of replaying its head.  Windows
    are seeded with one dynamic slice — called per reset, never per
    step, so the streaming-window fast path is unaffected.
    """
    t0 = jnp.asarray(t0, jnp.int32)
    # every data read is rebased by row0: a streamed shard carries its
    # global start row there (0 when fully resident), so cursors stay
    # global while array indices are shard-local
    r0 = data.row0
    state = initial_state(cfg)
    state = state._replace(t=t0)
    bar = read_bar(data, t0)
    state = broker.mark_to_market(state, bar["close"], params)
    state = state._replace(
        prev_equity_delta=state.equity_delta,
        price_window=jax.lax.dynamic_slice(
            data.padded_close, (t0 + 1 - r0,), (cfg.window_size,)
        ).astype(state.price_window.dtype),
        feat_window=jax.lax.dynamic_slice(
            data.padded_features,
            (t0 + 1 - r0, jnp.zeros((), jnp.int32)),
            (cfg.window_size, cfg.n_features),
        ),
    )
    return state, build_obs(state, data, cfg, params, bar=bar)


def step(
    cfg: EnvConfig,
    params: EnvParams,
    data: MarketData,
    state: EnvState,
    action,
) -> Tuple[EnvState, Dict[str, Any], Any, Any, Dict[str, Any]]:
    """Pure step. Returns (state, obs, reward, done, info)."""
    # the step's layers, by name, for a device trace (telemetry/scopes.py:
    # metadata only, the program does not change): every read of the tape
    # by bar index is `tape_read` (one packed row per distinct index,
    # data/feed.py read_bar), the obs windows and build_obs are `obs`, all
    # the rest is `dynamics`
    with jax.named_scope(scopes.ENV_STEP):
        return _step(cfg, params, data, state, action)


def _step(cfg, params, data, state, action):
    n = cfg.n_bars
    was_terminated = state.terminated

    with jax.named_scope(scopes.DYNAMICS):
        # ---- action coercion (reference app/env.py:343-360) ------------------
        raw = jnp.asarray(action).reshape(-1)[0].astype(state.pos.dtype)
        if cfg.action_space_mode == "continuous":
            thr = params.continuous_action_threshold
            a = jnp.where(raw >= thr, 1, jnp.where(raw <= -thr, 2, 0)).astype(jnp.int32)
        else:
            ai = jnp.asarray(action).reshape(-1)[0].astype(jnp.int32)
            hi = 3 if cfg.allow_flat_action else 2
            a = jnp.where((ai >= 0) & (ai <= hi), ai, 0)

    # ---- event-context overlay (reference app/env.py:394-440) ------------
    a, state, event_info = _event_overlay(state, a, data, cfg, params)

    with jax.named_scope(scopes.DYNAMICS):
        # ---- action diagnostics (post-overlay, reference app/env.py:287) -----
        # Post-termination steps are complete no-ops (the reference's driver
        # never steps a finished env, so its quirk of still counting
        # diagnostics there is unobservable; making them inert keeps the
        # scanned and step-by-step paths byte-identical).
        state = _record_action(state, raw, a, cfg, ~was_terminated)

        # ---- engine advance ---------------------------------------------------
        live = ~was_terminated
        advance = live & state.started & (state.t < n - 1)
        exhausted = live & state.started & (state.t >= n - 1)
        act_strategy = live & ~exhausted          # warmup or advancing step

        t_new = jnp.where(advance, state.t + 1, state.t)
    with jax.named_scope(scopes.TAPE_READ):
        # the new bar's packed row, fetched once; what no arm uses is dead
        bar = read_bar(data, t_new)
    o, h, l, c = bar["open"], bar["high"], bar["low"], bar["close"]
    mow = bar["minute_of_week"]
    # the rollover accrual (2b below) and the LOB venue's scenario bitmask
    # (feed=scengen), for the arms that use them
    accrual_rate = bar["rollover_accrual"] if cfg.financing_enabled else None
    scen = (
        bar["scen_flags"]
        if cfg.venue == "lob" and cfg.lob_flow_from_scengen else None
    )

    with jax.named_scope(scopes.DYNAMICS):
        st = state._replace(
            t=t_new, last_trade_cost=jnp.zeros_like(state.last_trade_cost)
        )

        # fused env-dynamics kernel dispatch (`rollout_env_kernel` knob,
        # docs/performance.md "MFU push"): the bar venue's
        # fill/bracket/financing and mark/reward chains as env-blocked
        # pallas kernels.  The off|on|interpret decision is
        # ops/dispatch.kernel_interpret's (None = the plain-XLA oracle);
        # EnvConfig validation already refused a non-bar venue.  All three
        # bitwise-identical by construction (ops/env_dynamics.py;
        # tests/test_env_dynamics_kernel.py).
        env_interpret = kernel_interpret(cfg.rollout_env_kernel)
        kernel_env = env_interpret is not None

        if cfg.venue == "lob":
            # 1+2 (LOB venue): the pending order walks the seeded book at
            # the open and brackets resolve against actual prints along the
            # bar's message flow (gymfx_tpu/lob/venue.py).  Static branch:
            # with venue unset the bar path below is traced bit-identically
            # and no LOB code reaches the hot path.
            from gymfx_tpu.lob import venue as lob_venue

            # feed=scengen: the generated tape's per-bar scenario bitmask
            # reshapes the order flow (droughts thin the book, crash bars
            # burst the flow)
            st_l = lob_venue.execute_bar(
                st, o, h, l, c, t_new, cfg, params, scen_flags=scen
            )
            st = _select(advance, st_l, st)
        elif kernel_env:
            # 1+2+2b fused (kernel A, ops/env_dynamics.py): the same
            # fill_pending -> check_brackets -> financing chain as below,
            # packed into one env-blocked pallas VMEM pass
            from gymfx_tpu.ops import env_dynamics

            st = env_dynamics.fused_fill_brackets(
                st, o, h, l, c, accrual_rate, advance, cfg, params,
                interpret=env_interpret,
            )
        else:
            # 1. pending order fills at the new bar's open (only when advancing)
            st_f = broker.fill_pending(st, o, params, cfg, h, l)
            st = _select(advance, st_f, st)
            # 2. brackets resolve against the new bar's H/L
            st_b = broker.check_brackets(st, o, h, l, cfg, params)
            st = _select(advance, st_b, st)
        # 2b. FX rollover financing: the position held at a rollover bar
        #     (first bar at/after 22:00 UTC of its day) accrues interest from
        #     the pair's daily rate differential, precomputed into
        #     data.rollover_accrual (data/financing.py).  One fused
        #     multiply-add per step — the scan twin of the replay engine's
        #     apply_rollover (simulation/replay.py) and of the reference's
        #     FXRolloverInterestModule (reference
        #     simulation_engines/nautilus_gym.py:276-290).  (Folded into
        #     kernel A on the fused path above.)
        if cfg.financing_enabled and not kernel_env:
            accrual = st.pos * c * accrual_rate
            st = st._replace(
                cash_delta=st.cash_delta + jnp.where(advance, accrual, 0.0)
            )
        # 3. strategy applies the (post-overlay) action at the bar close
        st = strategy.apply_action(st, a, o, h, l, c, mow, cfg, params, act_strategy)
        # 3b. margin preflight (profile-gated): deny entries whose opening
        # margin exceeds free cash (reference Nautilus env denial path,
        # simulation_engines/nautilus_gym.py:162-171; counter kept
        # engine-neutral as 'preflight_denied')
        if cfg.enforce_margin_preflight:
            opening = broker.opening_units(st.pos, st.pending_target)
            required = opening * c * params.margin_init
            if cfg.margin_model == "leveraged":
                required = required / jnp.maximum(params.leverage, 1e-12)
            # compare against the realized-balance account (NOT the
            # full-notional cash ledger, which would mis-gate flips of
            # leveraged positions) — same measure as the replay engine
            free = broker.realized_balance(st, params)
            denied = st.pending_active & (opening > 0) & (required > free)
            st = st._replace(
                pending_active=st.pending_active & ~denied,
                pending_target=jnp.where(denied, 0.0, st.pending_target),
                pending_sl=jnp.where(denied, 0.0, st.pending_sl),
                pending_tp=jnp.where(denied, 0.0, st.pending_tp),
                exec_diag=st.exec_diag.at[EXEC_DIAG_INDEX["preflight_denied"]].add(
                    denied.astype(jnp.int32)
                ),
            )
        # 4. mark equity at the close (advancing bars only; the warmup step
        #    re-marks bar 0, which is a no-op on an untouched ledger)
        if kernel_env:
            # 4 + reward fused (kernel B): mark, drawdown and the reward
            # carries in one VMEM pass.  The base reward is computed HERE —
            # nothing between this mark and the reward block below reads or
            # writes the equity deltas or reward carries, so the program is
            # identical with the reward hoisted to the mark.
            from gymfx_tpu.ops import env_dynamics

            st, _kernel_base_reward = env_dynamics.fused_mark_reward(
                st, c, advance | (live & ~state.started), live, cfg, params,
                interpret=env_interpret,
            )
        else:
            st_m = broker.mark_to_market(st, c, params)
            st = _select(advance | (live & ~state.started), st_m, st)
        # 4b. maintenance-margin closeout: equity marked below the position's
        #     maintenance requirement forces a liquidation that REPLACES any
        #     pending order and fills at the next bar's open through the
        #     ordinary order path (slippage and commission apply) — the scan
        #     twin of Nautilus' margin-account liquidation (reference
        #     simulation_engines/nautilus_adapter.py:397-427, margin_maint
        #     contracts.py:117-120).  The agent may re-enter afterwards
        #     (subject to the init-margin preflight), as on a real venue.
        if cfg.enforce_margin_closeout:
            maint = broker.maintenance_margin(st.pos, c, params, cfg.margin_model)
            equity_now = params.initial_cash + st.equity_delta
            # gated on `advance`: the exhausted terminal step re-visits the
            # same mark and would double-count the breach (and its forced
            # order could never fill — there is no next bar)
            breach = advance & (st.pos != 0) & (equity_now < maint)
            st = st._replace(
                pending_active=st.pending_active | breach,
                pending_target=jnp.where(breach, 0.0, st.pending_target),
                pending_sl=jnp.where(breach, 0.0, st.pending_sl),
                pending_tp=jnp.where(breach, 0.0, st.pending_tp),
                pending_forced=st.pending_forced | breach,
                exec_diag=st.exec_diag.at[EXEC_DIAG_INDEX["margin_closeouts"]].add(
                    breach.astype(jnp.int32)
                ),
            )

    # streaming obs windows: on advance, shift left and append the new
    # bar's close / raw feature row (raw row i lives at padded[i + w])
    with jax.named_scope(scopes.OBS):
        if cfg.include_prices:
            new_price = jnp.concatenate(
                [st.price_window[1:], c[None].astype(st.price_window.dtype)]
            )
            st = st._replace(
                price_window=jnp.where(advance, new_price, st.price_window)
            )
    if cfg.n_features > 0:
        with jax.named_scope(scopes.TAPE_READ):
            new_feat_row = data.padded_features[
                t_new + cfg.window_size - data.row0
            ]
        with jax.named_scope(scopes.OBS):
            new_feat = jnp.concatenate(
                [st.feat_window[1:], new_feat_row[None, :]]
            )
            st = st._replace(
                feat_window=jnp.where(advance, new_feat, st.feat_window)
            )

    with jax.named_scope(scopes.DYNAMICS):
        st = st._replace(started=state.started | live)

        # ---- reward -----------------------------------------------------------
        if kernel_env:
            base_reward = _kernel_base_reward  # computed inside kernel B
        else:
            st, base_reward = rewards.compute_reward(st, cfg, params, live)
        fc_row = jnp.minimum(st.t + 1, n - 1)
    with jax.named_scope(scopes.TAPE_READ):
        # one bar ahead: the force-close block here, and with the calendar
        # block again in build_obs / build_info, from the same row
        next_bar = read_bar(data, fc_row)
    with jax.named_scope(scopes.DYNAMICS):
        penalty = rewards.force_close_penalty(
            st, next_bar["force_close"], cfg, params
        )
        penalty = jnp.where(live, penalty, 0.0)
        reward = base_reward - penalty

        # ---- termination ------------------------------------------------------
        equity = params.initial_cash + st.equity_delta
        broke = equity <= params.min_equity
        terminated = was_terminated | exhausted | (live & broke)
        # explicit reason, latched at FIRST termination: bankruptcy wins over
        # exhaustion (a final-bar bankruptcy is a bankruptcy — the bar cursor
        # alone cannot tell them apart, types.py TERMINATION_*)
        from gymfx_tpu.core.types import TERMINATION_BANKRUPT, TERMINATION_EXHAUSTED

        reason_now = jnp.where(
            live & broke,
            jnp.int32(TERMINATION_BANKRUPT),
            jnp.where(exhausted, jnp.int32(TERMINATION_EXHAUSTED), jnp.int32(0)),
        )
        st = st._replace(
            terminated=terminated,
            termination_reason=jnp.where(
                was_terminated, st.termination_reason, reason_now
            ).astype(jnp.int32),
        )

    with jax.named_scope(scopes.OBS):
        obs = build_obs(st, data, cfg, params, bar=bar, next_bar=next_bar)
        info = build_info(
            st, data, cfg, params, event_info, bar=bar, next_bar=next_bar
        )
    info["reward"] = reward
    info["base_reward"] = base_reward
    info["force_close_reward_penalty"] = penalty
    info["pnl"] = st.equity_delta - st.prev_equity_delta
    info["trade_cost"] = st.last_trade_cost
    # full-precision equity relative to initial cash (info["equity"] is
    # initial+delta in f32, quantized at ~1e-3 on a 10k account)
    info["equity_delta"] = st.equity_delta
    # order/bracket state for the host-side audit trail (reference
    # GYMFX_BRACKET_AUDIT JSONL, strategy_plugins/direct_atr_sltp.py:40-50)
    info["pending_active"] = st.pending_active
    info["pending_target"] = st.pending_target
    info["pending_sl"] = st.pending_sl
    info["pending_tp"] = st.pending_tp
    info["bracket_sl"] = st.bracket_sl
    info["bracket_tp"] = st.bracket_tp
    info["position_units"] = st.pos
    info["termination_reason"] = st.termination_reason
    info["atr"] = jnp.where(
        st.tr_len > 0,
        jnp.sum(st.tr_buffer) / jnp.maximum(st.tr_len, 1).astype(st.tr_buffer.dtype),
        0.0,
    )
    return st, obs, reward, terminated, info


# ---------------------------------------------------------------------------
def _select(pred, a: EnvState, b: EnvState) -> EnvState:
    return EnvState(*(jnp.where(pred, x, y) for x, y in zip(a, b)))


def _event_overlay(state, a, data: MarketData, cfg: EnvConfig, params: EnvParams):
    """Event-context action transform (reference app/env.py:362-440).

    Reads engineered no-trade columns at the upcoming row and blocks new
    entries / force-flattens open positions during event windows."""
    n = cfg.n_bars
    row = jnp.minimum(jnp.minimum(state.t + 1, n), n - 1)
    with jax.named_scope(scopes.TAPE_READ):
        upcoming = read_bar(data, row)
    no_trade_value = upcoming["ev_no_trade"]
    spread_mult = upcoming["ev_spread_mult"]
    slip_mult = upcoming["ev_slip_mult"]
    with jax.named_scope(scopes.DYNAMICS):
        active = no_trade_value >= params.event_no_trade_threshold
        pos_sign = jnp.sign(state.pos).astype(jnp.int32)
        before = a

        live = ~state.terminated
        if cfg.event_context_execution_overlay:
            diag = state.exec_diag
            diag = diag.at[EXEC_DIAG_INDEX["event_context_no_trade_active_steps"]].add(
                (active & live).astype(jnp.int32)
            )
            forced_flat = (
                active & jnp.asarray(cfg.event_context_force_flat) & (pos_sign != 0)
            )
            blocked = (
                active
                & ~forced_flat
                & jnp.asarray(cfg.event_context_block_new_entries)
                & (pos_sign == 0)
                & ((before == 1) | (before == 2))
            )
            after = jnp.where(forced_flat, 3, jnp.where(blocked, 0, before))
            overridden = after != before
            diag = diag.at[EXEC_DIAG_INDEX["event_context_action_overrides"]].add(
                (overridden & live).astype(jnp.int32)
            )
            diag = diag.at[EXEC_DIAG_INDEX["event_context_blocked_entries"]].add(
                (blocked & live).astype(jnp.int32)
            )
            diag = diag.at[EXEC_DIAG_INDEX["event_context_forced_flat_actions"]].add(
                (forced_flat & live).astype(jnp.int32)
            )
            state = state._replace(exec_diag=diag)
        else:
            forced_flat = jnp.zeros_like(active)
            blocked = jnp.zeros_like(active)
            after = before

        event_info = {
            "event_context_no_trade_value": no_trade_value,
            "event_context_no_trade_active": active.astype(jnp.float32),
            "event_context_spread_stress_multiplier": spread_mult,
            "event_context_slippage_stress_multiplier": slip_mult,
            "event_context_execution_overlay": jnp.asarray(
                cfg.event_context_execution_overlay
            ),
            "event_context_action_before_overlay": before,
            "event_context_action_after_overlay": after,
            "event_context_action_overridden": after != before,
            "event_context_blocked_entry": blocked,
            "event_context_forced_flat": forced_flat,
            "event_context_position_before_overlay": pos_sign,
        }
    return after, state, event_info


def _record_action(state: EnvState, raw, a, cfg: EnvConfig, live) -> EnvState:
    """Per-episode action counters (reference app/env.py:744-761);
    inert when ``live`` is False (post-termination)."""
    one = live.astype(jnp.int32)
    diag = state.action_diag
    diag = diag.at[ACTION_DIAG_INDEX["steps"]].add(one)
    is_long = (a == 1) & live
    is_short = (a == 2) & live
    is_hold = ~is_long & ~is_short & live
    diag = diag.at[ACTION_DIAG_INDEX["long_actions"]].add(is_long.astype(jnp.int32))
    diag = diag.at[ACTION_DIAG_INDEX["short_actions"]].add(is_short.astype(jnp.int32))
    diag = diag.at[ACTION_DIAG_INDEX["non_hold_actions"]].add(
        (is_long | is_short).astype(jnp.int32)
    )
    diag = diag.at[ACTION_DIAG_INDEX["hold_actions"]].add(is_hold.astype(jnp.int32))
    if cfg.action_space_mode == "continuous":
        diag = diag.at[ACTION_DIAG_INDEX["continuous_deadband_actions"]].add(
            is_hold.astype(jnp.int32)
        )
    return state._replace(
        action_diag=diag,
        raw_abs_sum=state.raw_abs_sum + jnp.where(live, jnp.abs(raw), 0.0),
        raw_min=jnp.where(live, jnp.minimum(state.raw_min, raw), state.raw_min),
        raw_max=jnp.where(live, jnp.maximum(state.raw_max, raw), state.raw_max),
        last_raw_action=jnp.where(live, raw, state.last_raw_action),
        last_coerced_action=jnp.where(
            live, a.astype(jnp.int32), state.last_coerced_action
        ),
    )


import jax as _jax  # noqa: E402

_JIT_RESET = _jax.jit(reset, static_argnums=0)
_JIT_STEP = _jax.jit(step, static_argnums=0)
