"""Gymnasium adapter: API contract, space parity, summary shape
(reference tools/check_gym_compliance.py and app/env.py space layout)."""
import numpy as np
import pytest

from gymfx_tpu.gym_env import GymFxEnv, build_environment
from gymfx_tpu.data.feed import MarketDataset
from gymfx_tpu.config import DEFAULT_VALUES
from tests.helpers import uptrend_df


def _gym_env(**overrides):
    config = dict(DEFAULT_VALUES)
    config.update({"window_size": 8, "timeframe": "M1"})
    config.update(overrides)
    df = uptrend_df(80)
    return GymFxEnv(config, dataset=MarketDataset(df, config))


def test_gymnasium_check_env_passes():
    from gymnasium.utils.env_checker import check_env

    env = _gym_env()
    check_env(env, skip_render_check=True)


def test_observation_space_blocks_default():
    env = _gym_env()
    assert set(env.observation_space.spaces.keys()) == {
        "prices", "returns", "position", "equity_norm",
        "unrealized_pnl_norm", "steps_remaining_norm",
    }
    assert env.observation_space["prices"].shape == (8,)
    obs, info = env.reset()
    assert env.observation_space.contains(obs)


def test_stage_b_and_calendar_blocks_extend_space():
    env = _gym_env(stage_b_force_close_obs=True, broker_profile="oanda_us_fx")
    keys = set(env.observation_space.spaces.keys())
    assert {"bars_to_force_close", "hours_to_force_close", "is_force_close_zone",
            "is_monday_entry_window"} <= keys
    assert {"hours_to_fx_daily_break", "broker_market_open",
            "margin_closeout_percent", "margin_available_norm"} <= keys
    obs, info = env.reset()
    assert env.observation_space.contains(obs)
    assert "broker_market_open" in info


def test_step_contract_and_info_layout():
    env = _gym_env()
    obs, info = env.reset(seed=1)
    obs, reward, terminated, truncated, info = env.step(1)
    assert isinstance(reward, float)
    assert isinstance(terminated, bool) and isinstance(truncated, bool)
    for key in ("equity", "position", "price", "bar_index", "total_bars",
                "trades", "commission_paid", "raw_action_value",
                "coerced_action", "action_diagnostics",
                "execution_diagnostics", "reward", "base_reward", "pnl"):
        assert key in info, key
    assert info["action_diagnostics"]["steps"] == 1
    assert info["action_diagnostics"]["long_actions"] == 1


def test_continuous_action_space():
    env = _gym_env(action_space_mode="continuous")
    import gymnasium as gym

    assert isinstance(env.action_space, gym.spaces.Box)
    obs, info = env.reset()
    obs, r, term, trunc, info = env.step(np.array([0.9], np.float32))
    assert info["coerced_action"] == 1


def test_summary_keys_and_values():
    env = _gym_env(metrics_plugin="trading_metrics")
    obs, info = env.reset()
    done = False
    k = 0
    while not done and k < 60:
        obs, r, done, trunc, info = env.step(1 if k == 0 else 0)
        k += 1
    summary = env.summary()
    for key in ("initial_cash", "final_equity", "total_return",
                "max_drawdown_pct", "sharpe_ratio", "sqn", "trades_total",
                "trades_won", "trades_lost", "avg_trade_pnl", "rap",
                "risk_adjusted_total_return", "metric_schema",
                "action_diagnostics", "execution_diagnostics"):
        assert key in summary, key
    assert summary["total_return"] > 0  # buy&hold on the uptrend
    assert summary["metric_schema"] == "trading.metrics.v1"
    assert summary["trades_total"] == 0


def test_build_environment_dispatcher():
    config = dict(DEFAULT_VALUES)
    config.update({"window_size": 8, "input_data_file": "examples/data/eurusd_sample.csv"})
    env = build_environment(config=config)
    assert isinstance(env, GymFxEnv)
    with pytest.raises(ValueError, match="simulation_engine"):
        build_environment(config={**config, "simulation_engine": "magic"})


def test_bracket_audit_trail(tmp_path, monkeypatch):
    import json

    audit = tmp_path / "audit.jsonl"
    monkeypatch.setenv("GYMFX_BRACKET_AUDIT", str(audit))
    env = _gym_env(strategy_plugin="direct_fixed_sltp", sl_pips=20.0,
                   tp_pips=40.0, pip_size=0.0001)
    obs, info = env.reset()
    env.step(1)
    env.step(0)
    env.step(2)
    records = [json.loads(l) for l in audit.read_text().splitlines()]
    kinds = [r["kind"] for r in records]
    assert "long_bracket" in kinds and "short_bracket" in kinds
    long_rec = records[kinds.index("long_bracket")]
    assert long_rec["stop"] < long_rec["entry"] < long_rec["limit"]


def test_top_level_exports():
    import subprocess
    import sys

    import gymfx_tpu

    # lazy: importing the package must not pull in the heavy env/adapter
    # modules
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, gymfx_tpu; "
         "assert 'gymfx_tpu.gym_env' not in sys.modules; "
         "assert 'gymfx_tpu.core.runtime' not in sys.modules; "
         "assert 'Environment' in dir(gymfx_tpu)"],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    assert gymfx_tpu.GymFxEnv is GymFxEnv
    assert gymfx_tpu.build_environment is build_environment
    from gymfx_tpu.core.runtime import Environment
    from gymfx_tpu.vector_env import GymFxVectorEnv

    assert gymfx_tpu.Environment is Environment
    assert gymfx_tpu.GymFxVectorEnv is GymFxVectorEnv
    with pytest.raises(AttributeError):
        gymfx_tpu.nope


def test_all_obs_blocks_combined():
    # features + prices + agent state + stage-B + calendar in one env
    from tests.helpers import make_df

    n = 60
    rng = np.random.default_rng(0)
    closes = 1.1 + np.cumsum(rng.normal(0, 1e-4, n))
    df = make_df(closes, extra={"f1": rng.normal(size=n)})
    config = dict(DEFAULT_VALUES)
    config.update(window_size=8, timeframe="M1",
                  feature_columns=["f1"], include_price_window=True,
                  stage_b_force_close_obs=True, broker_profile="oanda_us_fx")
    env = GymFxEnv(config, dataset=MarketDataset(df, config))
    obs, info = env.reset()
    keys = set(env.observation_space.spaces)
    assert {"features", "prices", "returns", "position",
            "bars_to_force_close", "hours_to_fx_daily_break",
            "margin_available_norm"} <= keys
    assert env.observation_space.contains(obs)
    obs, r, d, t, info = env.step(1)
    assert env.observation_space.contains(obs)
    assert "is_no_trade_window" in info  # info-only calendar field
