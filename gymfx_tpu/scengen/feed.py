"""Generated scenarios -> the replay feed's exact host/device formats.

The bridge layer that makes ``feed=scengen`` indistinguishable from
``feed=replay`` downstream: generated paths land in a pandas DataFrame
on a weekend-skipping FX minute grid, and ``ScenGenDataset`` subclasses
``MarketDataset`` so EVERY derived tensor — NY-calendar features,
force-close windows, minute-of-week, leakage-safe scaler moments,
front-padded obs windows — comes from the same ``build_market_data``
code path replayed CSVs use.  The only addition is the per-bar
``scen_flags`` channel (params.FLAG_*), zero on replay feeds.

Spread blowouts ride the EXISTING event-context columns
(``event_spread_stress_multiplier`` / ``event_slippage_stress_multiplier``
-> ``ev_spread_mult`` / ``ev_slip_mult``), so droughts and crash spreads
reach the broker/obs through machinery that already exists.
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pandas as pd

from gymfx_tpu.data.feed import (
    MarketDataset,
    _infer_timeframe_hours,
    pack_bars,
)

from .params import ScenarioParams, scenario_params

DEFAULT_BARS = 2048
DEFAULT_PRESET = "regime_mix"
DEFAULT_PORTFOLIO_PAIRS = ("EUR_USD", "GBP_USD", "AUD_USD", "NZD_USD")

# representative initial price levels per pair (scenario tapes are
# synthetic — the level only matters for conversion/margin realism)
PAIR_S0 = {
    "EUR_USD": 1.10, "GBP_USD": 1.27, "AUD_USD": 0.66, "NZD_USD": 0.61,
    "USD_JPY": 148.0, "USD_CHF": 0.88, "USD_CAD": 1.36,
}

# quote-currency width of one unit of spread multiplier (the SPREAD
# column is informational; execution stress flows via the event columns)
BASE_SPREAD = 1.5e-5


def fx_timestamp_grid(
    n_bars: int, timeframe_hours: float, start: str = "2024-01-01"
) -> Tuple[pd.DatetimeIndex, np.ndarray]:
    """(timestamps, monday_open mask): ``n_bars`` sequential bars that
    skip the FX weekend close (Fri 22:00 -> Sun 22:00 UTC), so the
    generated tape has the same calendar edges — weekend gaps, Friday
    force-close windows, rollover bars — the calendar featureizer keys
    on.  ``monday_open[t]`` marks the first bar after each skip."""
    n = int(n_bars)
    step_min = max(1, int(round((timeframe_hours or 1 / 60) * 60)))
    step = pd.Timedelta(minutes=step_min)
    total = int(n * 7 / 5) + 2 * 1440 // step_min + 8
    while True:
        idx = pd.date_range(start, periods=total, freq=step)
        mins = idx.hour * 60 + idx.minute
        dow = idx.dayofweek
        closed = (
            ((dow == 4) & (mins >= 22 * 60))
            | (dow == 5)
            | ((dow == 6) & (mins < 22 * 60))
        )
        open_idx = idx[~closed]
        if len(open_idx) >= n:
            break
        total *= 2
    open_idx = open_idx[:n]
    monday = np.zeros(n, bool)
    if n > 1:
        gaps = np.diff(open_idx.values)
        monday[1:] = gaps > np.timedelta64(step_min, "m")
    return open_idx, monday


def _paths_to_frame(
    index: pd.DatetimeIndex, o, h, l, c, spread_mult, slip_mult
) -> pd.DataFrame:
    close = np.asarray(c, np.float64)
    high = np.asarray(h, np.float64)
    low = np.asarray(l, np.float64)
    df = pd.DataFrame(
        {
            "OPEN": np.asarray(o, np.float64),
            "HIGH": high,
            "LOW": low,
            "CLOSE": close,
            # deterministic activity proxy: bar range in 1e-4 fractions
            "VOLUME": np.round((high - low) / np.maximum(close, 1e-9) / 1e-4),
            "SPREAD": BASE_SPREAD * np.asarray(spread_mult, np.float64),
            "event_spread_stress_multiplier": np.asarray(
                spread_mult, np.float64
            ),
            "event_slippage_stress_multiplier": np.asarray(
                slip_mult, np.float64
            ),
        },
        index=index,
    )
    df.index.name = "DATE_TIME"
    return df


def _snap_to_tick(df: pd.DataFrame, tick: float) -> pd.DataFrame:
    """Snap generated OHLC onto the LOB's int-tick grid (f64 rounding,
    BEFORE the pipeline's f32 cast) so the tape satisfies the int16
    tick-delta wire format's on-grid requirement (data/compress.py).
    Rounding can push a bar's high below its open/close by half a tick;
    the hull is re-closed on the grid."""
    for col in ("OPEN", "HIGH", "LOW", "CLOSE"):
        df[col] = np.round(df[col].to_numpy(np.float64) / tick) * tick
    o, c = df["OPEN"].to_numpy(), df["CLOSE"].to_numpy()
    df["HIGH"] = np.maximum.reduce([df["HIGH"].to_numpy(), o, c])
    df["LOW"] = np.minimum.reduce([df["LOW"].to_numpy(), o, c])
    return df


def _maybe_snap(df: pd.DataFrame, config: Dict[str, Any]) -> pd.DataFrame:
    if not config.get("scengen_snap_to_tick"):
        return df  # default: bitwise-identical generation
    tick = float(config.get("lob_tick_size", 1e-5) or 1e-5)
    return _snap_to_tick(df, tick)


def _scengen_knobs(config: Dict[str, Any]) -> Tuple[str, int, int, float]:
    preset = str(config.get("scengen_preset") or DEFAULT_PRESET)
    n_bars = int(config.get("scengen_bars") or DEFAULT_BARS)
    seed = int(config.get("scengen_seed") or 0)
    tf_h = _infer_timeframe_hours(config) or 1 / 60
    return preset, n_bars, seed, tf_h


def synthesize_frame(
    config: Dict[str, Any]
) -> Tuple[pd.DataFrame, np.ndarray]:
    """Single-asset generation: (DataFrame, scen_flags) for the config's
    ``scengen_*`` knobs.  Deterministic in (preset, bars, seed,
    timeframe, start): the engine draws from one PRNGKey and threefry is
    backend-stable, so two processes produce bitwise-identical frames."""
    import jax

    from .engine import generate

    preset, n_bars, seed, tf_h = _scengen_knobs(config)
    p = scenario_params(preset)
    index, monday = fx_timestamp_grid(
        n_bars, tf_h, start=str(config.get("scengen_start", "2024-01-01"))
    )
    paths = generate(p, jax.random.PRNGKey(seed), n_bars, 1, monday)
    df = _paths_to_frame(
        index,
        np.asarray(paths.open)[:, 0], np.asarray(paths.high)[:, 0],
        np.asarray(paths.low)[:, 0], np.asarray(paths.close)[:, 0],
        np.asarray(paths.spread_mult), np.asarray(paths.slip_mult),
    )
    return _maybe_snap(df, config), np.asarray(paths.flags, np.int32)


def _parse_pairs(value: Any) -> List[str]:
    if value is None:
        return list(DEFAULT_PORTFOLIO_PAIRS)
    if isinstance(value, str):
        try:
            value = json.loads(value)
        except json.JSONDecodeError as e:
            raise ValueError(
                "scengen_pairs must be a JSON list of pair names "
                f"(e.g. '[\"EUR_USD\", \"GBP_USD\"]'), got {value!r}"
            ) from e
    if not isinstance(value, (list, tuple)) or not value:
        raise ValueError(
            f"scengen_pairs must be a non-empty list, got {value!r}"
        )
    return [str(p) for p in value]


def synthesize_portfolio_frames(
    config: Dict[str, Any]
) -> Tuple[List[str], Dict[str, pd.DataFrame], np.ndarray]:
    """Correlated multi-asset generation for the portfolio env:
    (pairs, per-pair aligned frames on one shared grid, scen_flags).
    Cross-asset correlation comes from the preset's Cholesky shock
    mixing; per-pair levels from PAIR_S0."""
    import jax

    from .engine import generate

    preset, n_bars, seed, tf_h = _scengen_knobs(config)
    pairs = _parse_pairs(config.get("scengen_pairs"))
    p = scenario_params(preset)
    s0 = np.asarray(
        [PAIR_S0.get(pair, 1.0) for pair in pairs], np.float32
    )
    p = p._replace(s0=s0)
    index, monday = fx_timestamp_grid(
        n_bars, tf_h, start=str(config.get("scengen_start", "2024-01-01"))
    )
    paths = generate(p, jax.random.PRNGKey(seed), n_bars, len(pairs), monday)
    o = np.asarray(paths.open)
    h = np.asarray(paths.high)
    l = np.asarray(paths.low)
    c = np.asarray(paths.close)
    sp = np.asarray(paths.spread_mult)
    sl = np.asarray(paths.slip_mult)
    aligned = {
        pair: _maybe_snap(
            _paths_to_frame(index, o[:, i], h[:, i], l[:, i], c[:, i],
                            sp, sl),
            config,
        )
        for i, pair in enumerate(pairs)
    }
    return pairs, aligned, np.asarray(paths.flags, np.int32)


class ScenGenDataset(MarketDataset):
    """A ``MarketDataset`` whose frame is generated instead of loaded.

    Everything downstream (Environment, BarStreamer, trainers) treats it
    exactly like a replayed dataset; the only difference is that
    ``build_market_data`` carries the generator's per-bar scenario flags
    into ``MarketData.scen_flags`` (zeros on every replay feed)."""

    def __init__(
        self,
        config: Dict[str, Any],
        dataframe: Optional[pd.DataFrame] = None,
        scen_flags: Optional[Sequence[int]] = None,
    ):
        if dataframe is None:
            dataframe, scen_flags = synthesize_frame(config)
        super().__init__(dataframe, config)
        if scen_flags is None or len(scen_flags) != len(dataframe):
            raise ValueError(
                "ScenGenDataset needs scen_flags aligned with its frame "
                f"(got {None if scen_flags is None else len(scen_flags)} "
                f"flags for {len(dataframe)} bars)"
            )
        self.scen_flags = np.asarray(scen_flags, np.int32)

    def build_market_data(self, **kwargs):
        md = super().build_market_data(**kwargs)
        if kwargs.get("device", True):
            import jax.numpy as jnp

            flags = jnp.asarray(self.scen_flags, jnp.int32)
        else:
            flags = np.asarray(self.scen_flags, np.int32)
        return pack_bars(md._replace(scen_flags=flags))

    def sliced(self, sl: slice) -> "ScenGenDataset":
        """Row-slice (chronological eval_split support) keeping frame
        and flags aligned."""
        return ScenGenDataset(
            self.config, self.dataframe.iloc[sl], self.scen_flags[sl]
        )
