"""Shared test fixtures: synthetic dataframes -> Environment."""
import numpy as np
import pandas as pd

from gymfx_tpu.config import DEFAULT_VALUES, merge_config
from gymfx_tpu.core.runtime import Environment
from gymfx_tpu.data.feed import MarketDataset


def make_df(closes, opens=None, highs=None, lows=None, start="2024-01-01", freq="1min",
            extra=None):
    closes = np.asarray(closes, dtype=np.float64)
    n = len(closes)
    df = pd.DataFrame(
        {
            "DATE_TIME": pd.date_range(start, periods=n, freq=freq),
            "OPEN": np.asarray(opens, np.float64) if opens is not None else closes,
            "HIGH": np.asarray(highs, np.float64) if highs is not None else closes,
            "LOW": np.asarray(lows, np.float64) if lows is not None else closes,
            "CLOSE": closes,
            "VOLUME": np.zeros(n),
        }
    )
    if extra:
        for k, v in extra.items():
            df[k] = v
    return df.set_index("DATE_TIME")


def make_env(df, **overrides):
    config = dict(DEFAULT_VALUES)
    config.update({"window_size": 4, "timeframe": "M1"})
    config.update(overrides)
    return Environment(config, dataset=MarketDataset(df, config))


def uptrend_df(n=40, start_price=1.1, rate=2e-4):
    closes = start_price * (1.0 + rate) ** np.arange(n)
    return make_df(closes, highs=closes + 1e-5, lows=closes - 1e-5)


def gather_op_paths(hlo_text):
    """The ``op_name`` path of every ``gather`` of an HLO text, inside a
    fusion or out: where a compiled step still looks rows up by index."""
    return [line.split('op_name="')[1].split('"')[0]
            for line in hlo_text.splitlines()
            if " gather(" in line and 'op_name="' in line]


def build_smoke_trainer(family, csv_path, csv2_path=None):
    """Tiny trainer fixture shared by the 2-process distributed smoke
    workers (subprocess scripts) and their in-process single-process
    references (tests/test_distributed_smoke.py, SURVEY §5.8).

    Returns ``(trainer, state_cls, params_field)`` — ``params_field``
    names the learner-parameter member used for fingerprinting."""
    from gymfx_tpu.config import DEFAULT_VALUES

    if family == "portfolio":
        from gymfx_tpu.core.portfolio import PortfolioEnvironment
        from gymfx_tpu.train.portfolio_ppo import (
            PortfolioPPOConfig,
            PortfolioPPOTrainer,
            PortfolioTrainState,
        )

        env = PortfolioEnvironment({
            "portfolio_files": {
                "EUR_USD": str(csv_path), "GBP_USD": str(csv2_path)
            },
            "window_size": 8,
            "initial_cash": 10000.0,
        })
        pcfg = PortfolioPPOConfig(n_envs=8, horizon=8, epochs=1, minibatches=2)
        return PortfolioPPOTrainer(env, pcfg), PortfolioTrainState, "params"

    from gymfx_tpu.core.runtime import Environment

    config = dict(DEFAULT_VALUES)
    config.update(input_data_file=str(csv_path), window_size=8,
                  timeframe="M1", num_envs=8,
                  policy_kwargs={"hidden": [16, 16]})
    if family == "ppo":
        from gymfx_tpu.train.ppo import PPOTrainer, TrainState, ppo_config_from

        config.update(ppo_horizon=8, ppo_epochs=1, ppo_minibatches=2)
        env = Environment(config)
        return PPOTrainer(env, ppo_config_from(config)), TrainState, "params"
    if family == "impala":
        from gymfx_tpu.train.impala import (
            ImpalaState,
            ImpalaTrainer,
            impala_config_from,
        )

        config.update(impala_unroll=8, policy="mlp")
        env = Environment(config)
        trainer = ImpalaTrainer(env, impala_config_from(config))
        return trainer, ImpalaState, "learner_params"
    raise ValueError(f"unknown smoke-trainer family {family!r}")
