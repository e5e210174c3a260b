"""Gymnasium-compatible shell over the functional core.

``GymFxEnv`` preserves the reference's external contract — Dict
observation space blocks (reference app/env.py:31-90 and the stage-B /
calendar extensions :174-207), Discrete(3)/Box action spaces, the
``reset/step/close/summary`` surface and the info dict layout
(:667-695) — while the actual stepping is one jitted XLA call instead
of a thread handshake.  Use it for single-env parity work and external
RL libraries; the scan rollout path is the throughput surface.

``build_environment`` mirrors the engine dispatcher
(reference gym_fx/__init__.py:4-12).  The legacy engine names map onto
the XLA scan engine: there is no backtrader/nautilus process here, the
scan kernel IS the simulation engine.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional

import numpy as np

try:
    import gymnasium as gym
    from gymnasium import spaces
except ImportError as exc:  # pragma: no cover
    raise ImportError("gymnasium is required for GymFxEnv") from exc

from gymfx_tpu.core.runtime import Environment
from gymfx_tpu.core.types import ACTION_DIAG_KEYS, EXEC_DIAG_KEYS
from gymfx_tpu.data.calendar import FORCE_CLOSE_FEATURE_KEYS
from gymfx_tpu.core.obs import CALENDAR_OBS_KEYS


def build_base_observation_space(
    config: Dict[str, Any], *, window_size: int
) -> spaces.Dict:
    """Reference-identical observation space declaration
    (reference app/env.py:31-90)."""
    feature_columns = list(config.get("feature_columns") or [])
    include_prices = bool(config.get("include_price_window", not feature_columns))
    include_agent_state = bool(config.get("include_agent_state", True))
    observation_spaces: Dict[str, spaces.Space] = {}

    if feature_columns:
        observation_spaces["features"] = spaces.Box(
            low=-np.inf,
            high=np.inf,
            shape=(window_size, len(feature_columns)),
            dtype=np.float32,
        )
    if include_prices:
        observation_spaces.update(
            {
                "prices": spaces.Box(-np.inf, np.inf, (window_size,), np.float32),
                "returns": spaces.Box(-np.inf, np.inf, (window_size,), np.float32),
            }
        )
    if include_agent_state:
        observation_spaces.update(
            {
                "position": spaces.Box(-1.0, 1.0, (1,), np.float32),
                "equity_norm": spaces.Box(-np.inf, np.inf, (1,), np.float32),
                "unrealized_pnl_norm": spaces.Box(-np.inf, np.inf, (1,), np.float32),
                "steps_remaining_norm": spaces.Box(0.0, 1.0, (1,), np.float32),
            }
        )
    if not observation_spaces:
        raise ValueError(
            "preprocessor observation contract emits no observation blocks"
        )
    return spaces.Dict(observation_spaces)


class GymFxEnv(gym.Env):
    """Single-env Gymnasium adapter over the jitted functional core."""

    metadata = {"render_modes": []}

    def __init__(self, config: Dict[str, Any], dataset=None):
        super().__init__()
        self._env = Environment(config, dataset=dataset)
        self.config = dict(self._env.config)
        cfg = self._env.cfg

        self.window_size = cfg.window_size
        self.initial_cash = float(self.config.get("initial_cash", 10000.0))
        self.total_bars = cfg.n_bars

        if cfg.action_space_mode == "continuous":
            self.action_space = spaces.Box(-1.0, 1.0, (1,), np.float32)
            self.continuous_action_threshold = float(
                self.config.get("continuous_action_threshold", 0.33) or 0.33
            )
        else:
            self.action_space = spaces.Discrete(3)
            self.continuous_action_threshold = None

        self.observation_space = build_base_observation_space(
            self.config, window_size=cfg.window_size
        )
        if cfg.stage_b_force_close_obs:
            extra = {
                "bars_to_force_close": spaces.Box(0.0, np.inf, (1,), np.float32),
                "hours_to_force_close": spaces.Box(0.0, np.inf, (1,), np.float32),
                "is_force_close_zone": spaces.Box(0.0, 1.0, (1,), np.float32),
                "is_monday_entry_window": spaces.Box(0.0, 1.0, (1,), np.float32),
            }
            self.observation_space = spaces.Dict(
                {**self.observation_space.spaces, **extra}
            )
        if cfg.oanda_fx_calendar_obs:
            extra = {}
            for key in CALENDAR_OBS_KEYS:
                high = (
                    1.0
                    if key.startswith("is_") or key == "broker_market_open"
                    else np.inf
                )
                extra[key] = spaces.Box(0.0, high, (1,), np.float32)
            extra["margin_closeout_percent"] = spaces.Box(0.0, np.inf, (1,), np.float32)
            extra["margin_available_norm"] = spaces.Box(0.0, np.inf, (1,), np.float32)
            self.observation_space = spaces.Dict(
                {**self.observation_space.spaces, **extra}
            )

        self._state = None
        self._last_info: Dict[str, Any] = {}
        self._equity_trace = []
        self._done_trace = []
        # Append-only JSONL audit of bracket decisions, gated by the same
        # env var as the reference (GYMFX_BRACKET_AUDIT,
        # reference direct_atr_sltp.py:40-50).  Only bracket strategies
        # audit, as in the reference (the audit lives in the atr plugin;
        # this framework extends it to direct_fixed_sltp with the same
        # record schema, atr fields null).
        self._audit_path = (
            os.environ.get("GYMFX_BRACKET_AUDIT")
            if self._env.cfg.strategy in ("direct_fixed_sltp", "direct_atr_sltp")
            else None
        )

    # ------------------------------------------------------------------
    def reset(self, *, seed: Optional[int] = None, options=None):
        super().reset(seed=seed)
        self._state, obs = self._env.reset()
        self._equity_trace = []
        self._done_trace = []
        self._last_info = {}
        return self._np_obs(obs), self._reset_info()

    def step(self, action):
        if self._state is None:
            raise RuntimeError("Call reset() before step().")
        self._state, obs, reward, done, info = self._env.step(self._state, action)
        # One batched device transfer for the whole step result:
        # per-scalar np.asarray would be a blocking device->host copy
        # each — ~60 per step — and dominates wall clock.
        import jax

        obs, reward, done, info = jax.device_get((obs, reward, done, info))
        py_info = self._py_info(info)
        self._last_info = py_info
        self._equity_trace.append(float(info["equity_delta"]))
        self._done_trace.append(bool(done))
        if self._audit_path:
            self._audit_emit(py_info)
        return self._np_obs(obs), float(reward), bool(done), False, py_info

    def _audit_emit(self, info: Dict[str, Any]) -> None:
        """Reference-schema audit records (direct_atr_sltp.py:164-168,
        242-247, 256-261): long_bracket/short_bracket entries with
        atr/k-multiple fields, session_force_close on session flatten."""
        if not info.get("pending_active"):
            return
        target = float(info.get("pending_target", 0.0))
        if target == 0.0:
            # Event-overlay force-flats are not audited in the reference
            # (action 3 is handled before the plugin, bt_bridge.py:178).
            if info.get("event_context_forced_flat"):
                return
            rec = {
                "kind": "session_force_close",
                "entry": info.get("price"),
                "size": float(info.get("position_units", 0.0)),
            }
        else:
            is_atr = self._env.cfg.strategy == "direct_atr_sltp"
            from gymfx_tpu.core.strategy import _effective_sltp_multiples

            if is_atr:
                k_sl_eff, k_tp_eff = _effective_sltp_multiples(
                    self._env.cfg, self._env.params
                )
                atr_fields = {
                    "atr": float(info.get("atr", 0.0)),
                    "k_sl_eff": float(k_sl_eff),
                    "k_tp_eff": float(k_tp_eff),
                    "sltp_risk_mode": self._env.cfg.sltp_risk_mode,
                }
            else:
                atr_fields = {
                    "atr": None,
                    "k_sl_eff": None,
                    "k_tp_eff": None,
                    "sltp_risk_mode": None,
                }
            rec = {
                "kind": "long_bracket" if target > 0 else "short_bracket",
                "entry": info.get("price"),
                "stop": float(info.get("pending_sl", 0.0)) or None,
                "limit": float(info.get("pending_tp", 0.0)) or None,
                "size": abs(target),
                "bar_index": info.get("bar_index"),
                **atr_fields,
            }
        try:
            with open(self._audit_path, "a", encoding="utf-8") as fh:
                fh.write(json.dumps(rec) + "\n")
        except OSError:
            pass

    def render(self):  # pragma: no cover
        return None

    def close(self):
        self._state = None

    # ------------------------------------------------------------------
    def _np_obs(self, obs) -> Dict[str, np.ndarray]:
        return {k: np.asarray(v, dtype=np.float32) for k, v in obs.items()}

    def _reset_info(self) -> Dict[str, Any]:
        # A minimal info at reset, like the reference warmup publish.
        import jax

        from gymfx_tpu.core.obs import build_info

        info = build_info(self._state, self._env.data, self._env.cfg, self._env.params)
        return self._py_info(jax.device_get(info))  # one batched transfer

    def _py_info(self, info) -> Dict[str, Any]:
        """Flat jnp info -> reference-shaped python info dict."""
        out: Dict[str, Any] = {}
        action_diag: Dict[str, Any] = {}
        exec_diag: Dict[str, Any] = {}
        for k, v in info.items():
            val = np.asarray(v).item() if hasattr(v, "item") or np.ndim(v) == 0 else v
            if k.startswith("action_diagnostics/"):
                action_diag[k.split("/", 1)[1]] = val
            elif k.startswith("execution_diagnostics/"):
                exec_diag[k.split("/", 1)[1]] = val
            else:
                out[k] = val
        steps = int(action_diag.get("steps", 0))
        if steps == 0:
            action_diag["raw_min"] = None
            action_diag["raw_max"] = None
        action_diag["continuous_action_threshold"] = self.continuous_action_threshold
        out["action_diagnostics"] = action_diag
        out["execution_diagnostics"] = exec_diag
        for key in ("broker_profile", "market_type", "trade_rate_band_id",
                    "calendar_policy_id"):
            if self._env.cfg.oanda_fx_calendar_obs and self.config.get(key) is not None:
                out[key] = self.config[key]
        return out

    # ------------------------------------------------------------------
    def summary(self) -> Dict[str, Any]:
        """Episode summary via the configured metrics plugin
        (reference app/env.py:697-716)."""
        from gymfx_tpu.metrics import compute_analyzers, summarize_default, summarize_trading
        from gymfx_tpu.plugins import get_plugin

        if self._state is not None and self._equity_trace:
            equity = self.initial_cash + np.asarray(self._equity_trace, np.float64)
            done = np.asarray(self._done_trace, bool)
            n_steps = len(self._equity_trace)
            ts = self._env.dataset.timestamps.iloc[1 : n_steps + 1] if len(
                self._env.dataset.timestamps
            ) else None
            analyzers = compute_analyzers(
                equity=equity, done=done, state=self._state, timestamps=ts
            )
            final_equity = float(equity[-1] if not done.any() else equity[int(np.argmax(done))])
        else:
            analyzers = {}
            final_equity = self.initial_cash

        name = str(self.config.get("metrics_plugin", "default_metrics"))
        summarize = {"default_metrics": summarize_default,
                     "trading_metrics": summarize_trading}.get(name)
        if summarize is None:
            summarize = get_plugin("metrics.plugins", name)(self.config)
        summary = summarize(
            initial_cash=self.initial_cash,
            final_equity=final_equity,
            analyzers=analyzers,
            config=self.config,
        )
        summary["action_diagnostics"] = dict(self._last_info.get("action_diagnostics", {}))
        summary["execution_diagnostics"] = dict(
            self._last_info.get("execution_diagnostics", {})
        )
        summary["event_context_diagnostics"] = {
            k: v for k, v in self._last_info.items() if k.startswith("event_context_")
        }
        return summary


def build_environment(*, config: Dict[str, Any], dataset=None, **_ignored) -> GymFxEnv:
    """Engine dispatcher (reference gym_fx/__init__.py:4-12).  All engine
    names resolve to the XLA scan engine; unknown names are rejected."""
    engine = str(config.get("simulation_engine", "scan")).lower()
    if engine not in ("scan", "backtrader", "nautilus"):
        raise ValueError(f"unsupported simulation_engine '{engine}'")
    return GymFxEnv(config, dataset=dataset)
