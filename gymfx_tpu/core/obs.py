"""Observation / info assembly (pure, static-structure dicts).

Obs blocks and semantics mirror the reference Dict observation space
(reference app/env.py:31-90 and the preprocessor family):
  features   (window, n_features) leakage-safe scaled feature window
             (reference preprocessor_plugins/feature_window_preprocessor.py)
  prices     (window,) close window, front-padded with the first value
  returns    (window,) first differences, 0 for the first element
             (reference preprocessor_plugins/default_preprocessor.py:47-53)
  position / equity_norm / unrealized_pnl_norm / steps_remaining_norm
             (1,) agent-state scalars
plus the optional stage-B force-close block (reference app/env.py:480-486)
and the OANDA calendar block (reference app/env.py:487-507).

Indexing parity note: the window at step ``t`` covers rows
[bar_index - window, bar_index) where bar_index = t+1 (the current row
inclusive), while calendar/force-close/event features are read at row
min(bar_index, n-1) — one bar ahead, the bar the pending action will
trade on — exactly as the reference indexes them
(reference app/env.py:465,481,489,369).
"""
from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

from gymfx_tpu.data.calendar import CALENDAR_FEATURE_KEYS, FORCE_CLOSE_FEATURE_KEYS
from gymfx_tpu.data.feed import MarketData, read_bar
from gymfx_tpu.core.types import (
    ACTION_DIAG_KEYS,
    EXEC_DIAG_KEYS,
    EnvConfig,
    EnvParams,
    EnvState,
)

# Calendar obs keys exclude is_no_trade_window (info-only in the obs dict,
# reference app/env.py:490-501) and add the two margin placeholders.
CALENDAR_OBS_KEYS = tuple(k for k in CALENDAR_FEATURE_KEYS if k != "is_no_trade_window")


def scale_feature_window(win, mean, std, neutral, cfg: "EnvConfig"):
    """THE O(1) leakage-safe scaling of one (window, F) feature block:
    z-score against the precomputed strictly-past moments, binary
    passthrough columns, clip, nan_to_num — in exactly this op order.

    Both obs producers go through this one definition — the training env
    (:func:`build_obs`) and the serving featurizer
    (serve/features.py, via the numpy twin below) — which is what makes
    serving observations bit-identical to training observations."""
    import jax.numpy as xp

    scaled = xp.where(neutral, 0.0, (win - mean) / std)
    if any(cfg.binary_mask):
        mask = xp.asarray(cfg.binary_mask, dtype=bool)
        scaled = xp.where(mask[None, :], win, scaled)
    clip = cfg.feature_clip
    if clip and clip > 0:
        scaled = xp.clip(scaled, -clip, clip)
    scaled = xp.nan_to_num(
        scaled, nan=0.0, posinf=clip or 0.0, neginf=-(clip or 0.0)
    )
    return scaled.astype(xp.float32)


def scale_feature_window_host(win, mean, std, neutral, cfg: "EnvConfig"):
    """Numpy twin of :func:`scale_feature_window` for the serving hot
    path (one request = one window; a device round trip per request
    would dominate the latency budget).  Every op is the elementwise
    IEEE-754 single-precision counterpart of the jnp version in the
    same order, so the result is bit-identical
    (tests/test_serve_features.py pins the two against each other)."""
    import numpy as xp

    win = xp.asarray(win, xp.float32)
    mean = xp.asarray(mean, xp.float32)
    std = xp.asarray(std, xp.float32)
    scaled = xp.where(neutral, xp.float32(0.0), (win - mean) / std)
    if any(cfg.binary_mask):
        mask = xp.asarray(cfg.binary_mask, dtype=bool)
        scaled = xp.where(mask[None, :], win, scaled)
    clip = cfg.feature_clip
    if clip and clip > 0:
        scaled = xp.clip(scaled, xp.float32(-clip), xp.float32(clip))
    scaled = xp.nan_to_num(
        scaled, nan=0.0, posinf=clip or 0.0, neginf=-(clip or 0.0)
    )
    return scaled.astype(xp.float32)


def _scaled_features(win, mean, std, neutral, cfg: "EnvConfig"):
    """Rollout feature-scaling dispatch (`rollout_obs_kernel` knob,
    docs/performance.md): the fused pallas per-step kernel or the
    plain-XLA oracle, as ops/dispatch.kernel_interpret decides
    (off|on|interpret).  All three are bitwise-identical by construction
    (the kernel body reproduces :func:`scale_feature_window` op for op;
    tests/test_ops.py + tests/test_rollout_obs_kernel.py pin it)."""
    from gymfx_tpu.ops.dispatch import kernel_interpret

    interpret = kernel_interpret(cfg.rollout_obs_kernel)
    if interpret is None:
        return scale_feature_window(win, mean, std, neutral, cfg)
    from gymfx_tpu.ops.window_zscore import fused_step_obs

    return fused_step_obs(
        win, mean, std, neutral,
        binary_mask=cfg.binary_mask, clip=cfg.feature_clip,
        interpret=interpret,
    )


def _rows(state, data, n, bar, next_bar):
    """The two packed tape rows obs and info are built from: the current
    bar ``state.t`` and the bar one ahead, ``min(state.t + 1, n - 1)``.
    The env step hands in the rows it already fetched; a reset reads."""
    if bar is None:
        bar = read_bar(data, state.t)
    if next_bar is None:
        next_bar = read_bar(data, jnp.minimum(state.t + 1, n - 1))
    return bar, next_bar


def build_obs(
    state: EnvState, data: MarketData, cfg: EnvConfig, params: EnvParams,
    *, bar=None, next_bar=None,
) -> Dict[str, Any]:
    n = cfg.n_bars
    step = jnp.minimum(state.t + 1, n)  # == bar_index, clamped
    r0 = data.row0  # shard-local rebase for streamed data (0 resident)
    bar, next_bar = _rows(state, data, n, bar, next_bar)
    obs: Dict[str, Any] = {}

    if cfg.n_features > 0:
        win = state.feat_window  # streaming carry == padded[step : step+w]
        mean = data.feat_mean[step - r0]
        std = data.feat_std[step - r0]
        neutral = data.feat_neutral[step - r0]
        obs["features"] = _scaled_features(win, mean, std, neutral, cfg)

    price = bar["close"]
    prices = None
    if cfg.include_prices:
        prices = state.price_window  # streaming carry
        returns = prices - jnp.concatenate([prices[:1], prices[:-1]])
        obs["prices"] = prices.astype(jnp.float32)
        obs["returns"] = returns.astype(jnp.float32)

    if cfg.include_agent_state:
        initial = jnp.where(params.initial_cash == 0, 1.0, params.initial_cash)
        pos_sign = jnp.sign(state.pos)
        ref_price = prices[-1] if prices is not None else price
        unrealized = pos_sign * (price - ref_price) * params.position_size
        obs["position"] = jnp.asarray([pos_sign], dtype=jnp.float32)
        obs["equity_norm"] = jnp.asarray(
            [state.equity_delta / initial], dtype=jnp.float32
        )
        obs["unrealized_pnl_norm"] = jnp.asarray(
            [unrealized / initial], dtype=jnp.float32
        )
        # explicit f32 reciprocal multiply instead of `/ n`: XLA rewrites
        # a constant-divisor division into this multiply at runtime but
        # constant-folds it to the correctly-rounded quotient when the
        # cursor is static (reset_at with literal t0) — the explicit form
        # produces the SAME bits on both paths, and on the serving host
        # twin (serve/features.py)
        import numpy as _np

        remaining = jnp.maximum(0, n - (state.t + 1)) * (
            _np.float32(1.0) / _np.float32(max(1, n))
        )
        obs["steps_remaining_norm"] = jnp.asarray([remaining], dtype=jnp.float32)

    if cfg.stage_b_force_close_obs:
        fc = next_bar["force_close"]
        for i, key in enumerate(FORCE_CLOSE_FEATURE_KEYS):
            obs[key] = fc[i][None]

    if cfg.oanda_fx_calendar_obs:
        cal = next_bar["calendar"]
        cal_map = dict(zip(CALENDAR_FEATURE_KEYS, cal))
        for key in CALENDAR_OBS_KEYS:
            obs[key] = cal_map[key][None]
        initial = jnp.where(params.initial_cash == 0, 1.0, params.initial_cash)
        # real-ledger margin ratio (the reference publishes 0.0 when its
        # bridge lacks a margin account, app/env.py:615-623; here the
        # ledger always has one): maintenance margin / equity, 1.0 = at
        # the liquidation boundary (core/broker.py margin_closeout_percent)
        from gymfx_tpu.core import broker as _broker

        obs["margin_closeout_percent"] = jnp.asarray(
            [_broker.margin_closeout_percent(state, price, params, cfg.margin_model)],
            dtype=jnp.float32,
        )
        obs["margin_available_norm"] = jnp.asarray(
            [(params.initial_cash + state.equity_delta) / initial],
            dtype=jnp.float32,
        )

    for name in cfg.obs_kernels:
        # registered third-party obs blocks (plugins/kernels.py)
        from gymfx_tpu.plugins import kernels as _k

        obs.update(_k.get_obs_kernel(name)(state, data, cfg, params))
    return obs


def build_info(
    state: EnvState,
    data: MarketData,
    cfg: EnvConfig,
    params: EnvParams,
    event_info: Dict[str, Any] | None = None,
    *, bar=None, next_bar=None,
) -> Dict[str, Any]:
    n = cfg.n_bars
    bar, next_bar = _rows(state, data, n, bar, next_bar)
    info: Dict[str, Any] = {
        "equity": params.initial_cash + state.equity_delta,
        "position": jnp.sign(state.pos).astype(jnp.int32),
        "price": bar["close"],
        "bar_index": state.t + 1,
        "total_bars": jnp.asarray(n, dtype=jnp.int32),
        "trades": state.trade_count,
        "commission_paid": state.commission_paid,
        "raw_action_value": state.last_raw_action,
        "coerced_action": state.last_coerced_action,
    }
    for i, key in enumerate(ACTION_DIAG_KEYS):
        info[f"action_diagnostics/{key}"] = state.action_diag[i]
    info["action_diagnostics/raw_abs_sum"] = state.raw_abs_sum
    info["action_diagnostics/raw_min"] = state.raw_min
    info["action_diagnostics/raw_max"] = state.raw_max
    for i, key in enumerate(EXEC_DIAG_KEYS):
        info[f"execution_diagnostics/{key}"] = state.exec_diag[i]
    if event_info:
        info.update(event_info)

    if cfg.stage_b_force_close_obs:
        fc = next_bar["force_close"]
        for i, key in enumerate(FORCE_CLOSE_FEATURE_KEYS):
            info[key] = fc[i]
    if cfg.oanda_fx_calendar_obs:
        cal = next_bar["calendar"]
        for i, key in enumerate(CALENDAR_FEATURE_KEYS):
            info[key] = cal[i]
        initial = jnp.where(params.initial_cash == 0, 1.0, params.initial_cash)
        from gymfx_tpu.core import broker as _broker

        info["margin_closeout_percent"] = _broker.margin_closeout_percent(
            state, bar["close"], params, cfg.margin_model
        ).astype(jnp.float32)
        info["margin_available_norm"] = (
            params.initial_cash + state.equity_delta
        ) / initial
    return info
