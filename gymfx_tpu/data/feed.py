"""Market data pipeline: CSV -> host dataset -> columnar device arrays.

Load semantics match the reference default data feed (reference
data_feed_plugins/default_data_feed.py:36-56): CSV via pandas, datetime
index from ``date_column`` with unparseable rows dropped, missing
OHLC columns backfilled from ``price_column``, VOLUME defaulted to 0.

Instead of wrapping rows in a backtrader feed object, the dataset is
resolved ONCE into static-shaped device arrays (``MarketData``): prices,
padded window sources, per-bar NY-calendar/force-close feature columns
and leakage-safe scaling moments.  Every per-step computation inside
``jit`` is then a ``dynamic_slice`` + fused elementwise math — no pandas,
no Python objects, no data-dependent shapes.

The env step reads the tape ONE PACKED ROW PER BAR INDEX: the per-bar
columns it reads (``BAR_COLUMNS``, in that order) ride side by side in
``MarketData.bars``, a table whose row is a bar, and ``read_bar`` fetches
the row once and takes it apart with static slices.  On the chip a
gather costs per index, not per byte: four columns read one by one at
the same index paid four times what the row of all twenty-four words
pays once (PERF.md, PR 27).  ``pack_bars`` is the one packer; every
producer of a ``MarketData`` ends in it, and whoever replaces a packed
column packs again.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np
import pandas as pd

from gymfx_tpu.data import calendar as fxcal

OHLC_COLUMNS = ("OPEN", "HIGH", "LOW", "CLOSE")

# The per-bar columns the env step reads by bar index, in the order they
# ride in a packed row: the new bar's prices, financing rate, session
# minute and scenario bitmask (read at the new bar), the event overlay's
# three (read at the upcoming bar) and the force-close and calendar
# blocks (read one bar ahead).  ``volume`` and the feature tables are not
# read by bar index in the step and stay out.
BAR_COLUMNS = (
    "open", "high", "low", "close", "rollover_accrual",
    "minute_of_week", "scen_flags",
    "ev_no_trade", "ev_spread_mult", "ev_slip_mult",
    "force_close", "calendar",
)


class MarketData(NamedTuple):
    """Static-shaped per-dataset device arrays consumed by the env kernel.

    All arrays are time-major over ``n`` bars.  ``padded_close`` /
    ``padded_features`` are front-padded with the first row so the obs
    window at step ``t`` is a pure ``dynamic_slice`` at offset ``t``
    (reference front-pad semantics:
    preprocessor_plugins/default_preprocessor.py:47-52).

    ``bars`` holds the ``BAR_COLUMNS`` a second time, packed by
    :func:`pack_bars` into one ``(n, words)`` table per word width — one
    table of 24 words (open, high, low, close, rollover_accrual,
    minute_of_week, scen_flags, ev_no_trade, ev_spread_mult, ev_slip_mult,
    force_close x4, calendar x10) when the compute dtype is 32 bits wide,
    the prices apart from the 32-bit columns when it is not.  The env step
    reads ONLY the table (:func:`read_bar`); the columns stay for their
    other readers (crosscheck, the portfolio's account marks, the padded
    windows, host-side tools).  A ``_replace`` of a packed column leaves
    the table stale: pack again.
    """

    open: Any          # (n,) compute dtype
    high: Any          # (n,)
    low: Any           # (n,)
    close: Any         # (n,)
    volume: Any        # (n,)
    padded_close: Any  # (n + window_size,)
    minute_of_week: Any  # (n,) int32, -1 when timestamp invalid
    calendar: Any      # (n, 10) float32 — fxcal.CALENDAR_FEATURE_KEYS order
    force_close: Any   # (n, 4) float32 — fxcal.FORCE_CLOSE_FEATURE_KEYS order
    ev_no_trade: Any   # (n,) float32
    ev_spread_mult: Any  # (n,) float32
    ev_slip_mult: Any  # (n,) float32
    rollover_accrual: Any  # (n,) compute dtype — daily financing rate on
                           # rollover bars, 0 elsewhere (data/financing.py)
    padded_features: Any  # (n + window_size, F) float32 (F may be 0)
    feat_mean: Any     # (n + 1, F) float32 — scaler mean fit on strictly-past rows
    feat_std: Any      # (n + 1, F) float32
    feat_neutral: Any  # (n + 1,) bool — True => neutral zero warm-up window
    # global bar row of local array index 0.  Always 0 for a fully
    # resident dataset; a streamed shard (shard_market_data) carries the
    # shard's start row here so the env kernel can keep GLOBAL bar
    # cursors (state.t) and rebase every array read by -row0 — one
    # compiled program serves every shard.
    row0: Any = 0
    # (n,) int32 per-bar scenario bitmask (scengen/params.py FLAG_*):
    # zeros on every replayed feed; generated feeds carry the active
    # regime/overlay so venue=lob can thin its flow with the tape (the
    # step uses it only under the static lob_flow_from_scengen flag).
    scen_flags: Any = 0
    # tuple of (n, words) tables, one per word width: BAR_COLUMNS packed
    # row by row (pack_bars); what the env step reads (read_bar)
    bars: Any = ()

    @property
    def n_bars(self) -> int:
        return int(self.close.shape[0])


def _bar_layout(data: MarketData):
    """How ``BAR_COLUMNS`` ride in ``data.bars``, from the columns' own
    dtypes and shapes (static under ``jit``): one table per word width, in
    order of first appearance, each a list of ``(field, first word, words,
    dtype, trailing shape)``.  A table's dtype is that of its first field;
    a field of another dtype of the same width rides as its bits."""
    tables: Dict[int, list] = {}
    for name in BAR_COLUMNS:
        col = getattr(data, name)
        # scen_flags left at its scalar default is a column of that value
        dtype = np.dtype(getattr(col, "dtype", np.int32))
        tail = tuple(np.shape(col)[1:])
        fields = tables.setdefault(dtype.itemsize, [])
        first = sum(f[2] for f in fields)
        fields.append((name, first, int(np.prod(tail, dtype=int)), dtype, tail))
    return list(tables.values())


def _as_bits(x, dtype):
    """``x`` as ``dtype`` of the same width, bit for bit (never a value
    conversion: the int32 minute_of_week rides in a float32 table)."""
    if x.dtype == dtype:
        return x
    if isinstance(x, np.ndarray):
        return x.view(dtype)
    import jax

    return jax.lax.bitcast_convert_type(x, dtype)


def pack_bars(data: MarketData) -> MarketData:
    """``data`` with ``bars`` (re)built from its per-bar columns — THE
    packer, for every producer (host numpy or device arrays, traced or
    not) and after every replacement of a packed column."""
    if isinstance(data.close, np.ndarray):
        xp = np
    else:
        import jax.numpy as xp
    n = data.close.shape[0]
    tables = []
    for fields in _bar_layout(data):
        carrier = fields[0][3]
        cols = []
        for name, _first, words, dtype, _tail in fields:
            col = getattr(data, name)
            if np.ndim(col) == 0:
                col = xp.full((n,), col, dtype)
            cols.append(_as_bits(col.reshape(n, words), carrier))
        tables.append(xp.concatenate(cols, axis=1))
    return data._replace(bars=tuple(tables))


def read_bar(data: MarketData, index) -> Dict[str, Any]:
    """The packed row of global bar ``index``, taken apart: ``{field:
    value}`` for every field of ``BAR_COLUMNS``, each with the dtype, the
    trailing shape and the bits ``data.<field>[index - data.row0]`` has.

    One gather per table and index — a field nobody uses is dead code, a
    table nobody uses is no gather at all.  Out of range the index is
    wrapped (negative) and clamped exactly like a column's ``[index]``.

    Two things here are what the chip's compiler made of the alternatives
    (PERF.md, PR 27; both are the same values either way):

    * ``.at[i].get`` and not ``table[i]``: scalar indexing of a 2-D table
      lowers to a dynamic slice with a start index PER DIMENSION, and the
      gather pays for each (1.31 against 0.61 ms for 131,072 rows);
    * the fields leave through ``optimization_barrier``, as arrays of their
      own.  Under the trainers' ``vmap`` the row is an ``(envs, words)``
      array, env-major with the words padded to 128 lanes; fields sliced
      straight out of it hand that layout on to whatever they are
      concatenated with, and the step's carried windows turned env-major
      with it (flagship: obs 8 -> 114 ms, dynamics 20 -> 89 ms a train
      step).  Behind the barrier a field is a plain vector over the envs,
      as a column's gather was.
    """
    import jax

    if not data.bars:
        raise ValueError(
            "MarketData.bars is empty: build the tape through pack_bars "
            "(every producer in gymfx_tpu does)"
        )
    i = index - data.row0  # shard-local rebase (0 when fully resident)
    out: Dict[str, Any] = {}
    for table, fields in zip(data.bars, _bar_layout(data)):
        row = table.at[i].get(mode="clip")
        for name, first, words, dtype, tail in fields:
            out[name] = _as_bits(row[first:first + words].reshape(tail), dtype)
    return jax.lax.optimization_barrier(out)


def _infer_timeframe_hours(config: Dict[str, Any]) -> float:
    """Timeframe label ('M1', 'h4', 'xx_15m', ...) -> hours (reference app/env.py:510-528)."""
    raw = str(
        config.get("timeframe")
        or config.get("timeframe_label")
        or config.get("bar_timeframe")
        or ""
    ).strip().lower()
    if "_" in raw:
        raw = raw.rsplit("_", 1)[-1]
    try:
        if raw.endswith("m") and raw[:-1].isdigit():
            return max(0.0, int(raw[:-1]) / 60.0)
        if raw.endswith("h") and raw[:-1].isdigit():
            return float(int(raw[:-1]))
        if raw.endswith("d") and raw[:-1].isdigit():
            return float(int(raw[:-1]) * 24)
        # leading-letter style: M1 / H4 / D1
        if raw[:1] == "m" and raw[1:].isdigit():
            return max(0.0, int(raw[1:]) / 60.0)
        if raw[:1] == "h" and raw[1:].isdigit():
            return float(int(raw[1:]))
        if raw[:1] == "d" and raw[1:].isdigit():
            return float(int(raw[1:]) * 24)
    except ValueError:
        return 0.0
    return 0.0


class MarketDataset:
    """Host-side dataset: the loaded dataframe + device-array builders."""

    def __init__(self, dataframe: pd.DataFrame, config: Dict[str, Any]):
        self.dataframe = dataframe
        self.config = dict(config)
        self.date_column = str(config.get("date_column", "DATE_TIME"))
        self.price_column = str(config.get("price_column", "CLOSE"))
        self.timeframe_hours = _infer_timeframe_hours(config)
        if isinstance(dataframe.index, pd.DatetimeIndex):
            self.timestamps = pd.Series(dataframe.index)
        elif self.date_column in dataframe.columns:
            self.timestamps = pd.to_datetime(
                dataframe[self.date_column], errors="coerce"
            ).reset_index(drop=True)
        else:
            self.timestamps = pd.Series(pd.DatetimeIndex([pd.NaT] * len(dataframe)))

    def __len__(self) -> int:
        if self.dataframe is None:
            return self._released_len
        return len(self.dataframe)

    def release_frame(self) -> None:
        """Drop the host dataframe once the device tensors exist.

        Large generated feeds (feed=scengen at big ``scengen_bars``)
        otherwise hold the f64 frame AND its encoded device form at the
        same time; timestamps and length survive so latency validation
        and ``len()`` keep working.  Building market data again after a
        release fails loudly."""
        if self.dataframe is not None:
            self._released_len = len(self.dataframe)
            self.dataframe = None

    def bar_interval_ms(self) -> Optional[float]:
        """Milliseconds per bar: from the timeframe label when present,
        else the median spacing of valid timestamps; None when neither
        is available (callers that need it must reject, not guess)."""
        if self.timeframe_hours:
            return self.timeframe_hours * 3_600_000.0
        ts = pd.to_datetime(self.timestamps, errors="coerce").dropna()
        if len(ts) < 2:
            return None
        deltas = ts.diff().dropna().dt.total_seconds()
        median = float(deltas.median())
        return median * 1000.0 if median > 0 else None

    # ------------------------------------------------------------------
    def build_market_data(
        self,
        *,
        window_size: int,
        feature_columns: Sequence[str] = (),
        feature_scaling: str = "rolling_zscore",
        feature_scaling_window: int = 256,
        dtype: Any = np.float32,
        event_context_no_trade_column: str = "event_no_trade_window_active",
        event_context_spread_stress_column: str = "event_spread_stress_multiplier",
        event_context_slippage_stress_column: str = "event_slippage_stress_multiplier",
        force_close_dow: int = 4,
        force_close_hour: int = 20,
        force_close_window_hours: int = 4,
        monday_entry_window_hours: int = 4,
        financing_rate_data: Any = None,
        instrument: str = "EUR_USD",
        device: bool = True,
    ) -> MarketData:
        df = self.dataframe
        if df is None:
            raise ValueError(
                "this dataset's frame was released (release_frame) after "
                "its device tensors were built — market data cannot be "
                "rebuilt from it"
            )
        n = len(df)
        if n < window_size + 2:
            raise ValueError("input data is empty or too short for the configured window")

        close = df[self.price_column].to_numpy(dtype=np.float64, copy=False)

        def col(name: str, fallback) -> np.ndarray:
            if name in df.columns:
                return df[name].to_numpy(dtype=np.float64, copy=False)
            if np.isscalar(fallback):
                return np.full(n, float(fallback), dtype=np.float64)
            return fallback

        o = col("OPEN", close)
        h = col("HIGH", close)
        l = col("LOW", close)
        c = col("CLOSE", close)
        v = col("VOLUME", 0.0)

        padded_close = np.concatenate([np.full(window_size, close[0]), close])

        tf_h = self.timeframe_hours or 1.0
        cal = fxcal.precompute_fx_calendar_features(
            self.timestamps, timeframe_hours=tf_h
        )
        fcz = fxcal.precompute_force_close_features(
            self.timestamps,
            timeframe_hours=self.timeframe_hours,
            force_close_dow=force_close_dow,
            force_close_hour=force_close_hour,
            force_close_window_hours=force_close_window_hours,
            monday_entry_window_hours=monday_entry_window_hours,
        )
        mow = fxcal.precompute_minute_of_week(self.timestamps)

        ev_no_trade = col(event_context_no_trade_column, 0.0).astype(np.float32)
        ev_spread = col(event_context_spread_stress_column, 1.0).astype(np.float32)
        ev_slip = col(event_context_slippage_stress_column, 1.0).astype(np.float32)

        if financing_rate_data is not None:
            from gymfx_tpu.data import financing as fxfin

            base_ccy, quote_ccy = fxfin.split_pair(instrument)
            accrual = fxfin.precompute_rollover_accrual(
                self.timestamps, financing_rate_data, base_ccy, quote_ccy
            )
        else:
            accrual = np.zeros(n, dtype=np.float64)

        padded_features, feat_mean, feat_std, feat_neutral = _build_feature_tensors(
            df,
            feature_columns=tuple(feature_columns),
            window_size=window_size,
            scaling=feature_scaling,
            scaling_window=feature_scaling_window,
        )

        import jax.numpy as jnp

        # device=False keeps every array on the host (numpy, same final
        # dtypes) so streaming callers can slice shards cheaply and
        # device_put them on their own schedule (BarStreamer).
        if device:
            def A(x, dt):
                return jnp.asarray(x, dtype=dt)
        else:
            def A(x, dt):
                return np.asarray(x, dtype=dt)

        f32 = np.float32
        return pack_bars(MarketData(
            open=A(o, dtype),
            high=A(h, dtype),
            low=A(l, dtype),
            close=A(c, dtype),
            volume=A(v, dtype),
            padded_close=A(padded_close, dtype),
            minute_of_week=A(mow, np.int32),
            calendar=A(cal, f32),
            force_close=A(fcz, f32),
            ev_no_trade=A(ev_no_trade, f32),
            ev_spread_mult=A(ev_spread, f32),
            ev_slip_mult=A(ev_slip, f32),
            rollover_accrual=A(accrual, dtype),
            padded_features=A(padded_features, f32),
            feat_mean=A(feat_mean, f32),
            feat_std=A(feat_std, f32),
            feat_neutral=A(feat_neutral, bool),
            row0=np.int32(0),
            scen_flags=A(np.zeros(n, np.int32), np.int32),
        ))


def _build_feature_tensors(
    df: pd.DataFrame,
    *,
    feature_columns: Tuple[str, ...],
    window_size: int,
    scaling: str,
    scaling_window: int,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Feature matrix + per-step leakage-safe scaler moments.

    The reference re-fits a z-score over up to ``feature_scaling_window``
    strictly-past rows per step per env (reference
    preprocessor_plugins/feature_window_preprocessor.py:174-191) — the
    obs hot spot.  Here the mean/std for every possible step are derived
    once from f64 cumulative moments: O(n·F) precompute, O(1) lookup per
    step in-graph.  Windows with <2 history rows are flagged neutral
    (zero warm-up, reference :112-117).
    """
    n = len(df)
    f = len(feature_columns)
    if f == 0:
        return (
            np.zeros((n + window_size, 0), np.float32),
            np.zeros((n + 1, 0), np.float32),
            np.ones((n + 1, 0), np.float32),
            np.zeros((n + 1,), bool),
        )
    missing = [cname for cname in feature_columns if cname not in df.columns]
    if missing:
        raise ValueError(
            "feature_window preprocessor: configured feature_columns "
            f"missing from dataframe: {missing[:5]}{'...' if len(missing) > 5 else ''}"
        )
    values = df[list(feature_columns)].to_numpy(dtype=np.float64)
    padded = np.concatenate([np.tile(values[0], (window_size, 1)), values], axis=0)

    if scaling == "none":
        mean = np.zeros((n + 1, f), np.float64)
        std = np.ones((n + 1, f), np.float64)
        neutral = np.zeros((n + 1,), bool)
        return padded.astype(np.float32), mean.astype(np.float32), std.astype(np.float32), neutral

    s1 = np.concatenate([np.zeros((1, f)), np.cumsum(values, axis=0)], axis=0)
    s2 = np.concatenate([np.zeros((1, f)), np.cumsum(values**2, axis=0)], axis=0)
    t = np.arange(n + 1)
    if scaling == "rolling_zscore":
        lo = np.maximum(0, t - int(scaling_window))
    elif scaling == "expanding_zscore":
        lo = np.zeros(n + 1, dtype=np.int64)
    else:
        raise ValueError(
            "feature_scaling must be one of ('none', 'rolling_zscore', "
            f"'expanding_zscore'); got {scaling!r}"
        )
    count = (t - lo).astype(np.float64)
    safe_count = np.maximum(count, 1.0)[:, None]
    mean = (s1[t] - s1[lo]) / safe_count
    var = (s2[t] - s2[lo]) / safe_count - mean**2
    std = np.sqrt(np.maximum(var, 0.0))
    std = np.where(std < 1e-8, 1.0, std)
    neutral = count < 2
    mean = np.where(neutral[:, None], 0.0, mean)
    std = np.where(neutral[:, None], 1.0, std)
    return (
        padded.astype(np.float32),
        mean.astype(np.float32),
        std.astype(np.float32),
        neutral,
    )


def market_data_nbytes(data: MarketData) -> int:
    """Total array bytes of a MarketData pytree (host or device)."""
    total = 0
    for leaf in data:
        # `bars` is a tuple of tables; every other field one array or scalar
        for arr in leaf if isinstance(leaf, tuple) else (leaf,):
            nbytes = getattr(arr, "nbytes", None)
            if nbytes is not None:
                total += int(nbytes)
    return total


def market_data_nbytes_report(data: MarketData, tape=None) -> Dict[str, Any]:
    """Decoded vs compressed byte accounting for one tape.

    ``decoded`` is the full-width f32 footprint of ``data``;
    ``compressed`` is the int16/packed footprint of its
    :class:`~gymfx_tpu.data.compress.CompressedTape` (None when the tape
    is not compressed), with ``ratio = decoded_per_shard * num_shards /
    compressed`` as defined by the tape."""
    decoded = market_data_nbytes(data) if data is not None else None
    if tape is None:
        return {"decoded": decoded, "compressed": None, "ratio": None}
    return {
        "decoded": decoded if decoded is not None
        else tape.decoded_shard_nbytes * tape.num_shards,
        "compressed": tape.nbytes,
        "ratio": tape.compression_ratio,
    }


def shard_market_data(data: MarketData, start: int, shard_bars: int,
                      window_size: int) -> MarketData:
    """Slice one streaming shard out of a (host) MarketData.

    A shard anchored at global row ``start`` serves env steps whose bar
    cursor lands in ``[start, start + shard_bars)``; a step at cursor
    ``t`` also reads row ``t + 1`` (next-bar fills, event overlay), so
    the bar arrays carry one row of lookahead and the front-padded
    window sources carry ``window_size`` extra rows.  ``row0 = start``
    lets the env kernel keep its GLOBAL cursor and rebase each read —
    every shard has identical shapes, so one compiled program serves
    them all.
    """
    hi = start + int(shard_bars) + 1
    if hi > int(np.asarray(data.close).shape[0]):
        raise ValueError(
            f"shard [{start}, {hi}) exceeds dataset of "
            f"{np.asarray(data.close).shape[0]} bars"
        )
    bar = slice(start, hi)
    padded = slice(start, hi + int(window_size))
    # scaler moments are (n + 1)-row tables indexed at min(t + 1, n):
    # one more row of lookahead than the bar arrays
    feat = slice(start, hi + 1)
    return data._replace(
        open=data.open[bar],
        high=data.high[bar],
        low=data.low[bar],
        close=data.close[bar],
        volume=data.volume[bar],
        padded_close=data.padded_close[padded],
        minute_of_week=data.minute_of_week[bar],
        calendar=data.calendar[bar],
        force_close=data.force_close[bar],
        ev_no_trade=data.ev_no_trade[bar],
        ev_spread_mult=data.ev_spread_mult[bar],
        ev_slip_mult=data.ev_slip_mult[bar],
        rollover_accrual=data.rollover_accrual[bar],
        padded_features=data.padded_features[padded],
        feat_mean=data.feat_mean[feat],
        feat_std=data.feat_std[feat],
        feat_neutral=data.feat_neutral[feat],
        row0=np.int32(start),
        scen_flags=data.scen_flags[bar],
        bars=tuple(table[bar] for table in data.bars),
    )


class BarStreamer:
    """Double-buffered host→device streaming of a long bar history.

    When the resident dataset would blow the HBM budget, the bar history
    is cut into fixed-size shards (identical static shapes — every shard
    reuses ONE compiled rollout executable) and each shard's
    ``jax.device_put`` is issued BEFORE compute is dispatched on the
    previous one, so the host→device DMA of shard ``t+1`` overlaps the
    device compute on shard ``t``.  At most two shards are resident at
    any time, which is why each shard targets half the budget.

    ``compress != "off"`` switches the wire format to int16 tick-deltas
    (data/compress.py): the planner then budgets on the COMPRESSED
    resident size plus two decoded shards (the double buffer), the whole
    compressed tape stays device-resident when the ring capacity allows,
    and ``_device_shard`` materializes each f32 shard with the fused
    decode — bitwise-identical to the uncompressed slice, verified at
    encode time.  The host f32 tape is dropped after encoding so large
    generated feeds never hold both representations at once.
    """

    def __init__(self, host_data: MarketData, *, window_size: int,
                 budget_mb: float, min_shard_bars: int = 64,
                 placement=None, compress: str = "off",
                 tick_size: float = 1e-5, what: str = ""):
        from gymfx_tpu.data import compress as C

        self.compress = C.validate_compress_mode(compress)
        self.window_size = int(window_size)
        # optional jax.sharding.Sharding for each shard's device_put —
        # on a mesh the ShardedRuntime passes its replicated sharding so
        # streamed bars land on EVERY mesh device (a bare device_put
        # targets device 0 only, forcing an implicit transfer inside the
        # sharded rollout program); None keeps the single-device path
        self.placement = placement
        n = int(np.asarray(host_data.close).shape[0])
        total = market_data_nbytes(host_data)
        per_bar = max(1.0, total / max(1, n))
        budget_bytes = float(budget_mb) * 2**20
        if self.compress == "off":
            shard_bars = (
                int(budget_bytes / 2.0 / per_bar) - self.window_size - 1
            )
        else:
            # two DECODED f32 buffers take an eighth of the budget; the
            # rest holds the compressed resident ring (checked below
            # once the actual compressed size is known)
            shard_bars = (
                int(budget_bytes * 0.125 / 2.0 / per_bar)
                - self.window_size - 1
            )
        shard_bars = max(int(min_shard_bars), shard_bars)
        if shard_bars >= n - 1:
            raise ValueError(
                f"dataset ({n} bars, {total / 2**20:.1f} MiB) fits the "
                f"{budget_mb} MiB streaming budget — streaming is not "
                "needed; unset stream_hbm_budget_mb"
            )
        self.n_bars = n
        self.shard_bars = shard_bars
        # regular starts every shard_bars; the final shard is anchored so
        # its lookahead row is the last bar — it overlaps the previous
        # shard, keeping every shard the same static shape.
        starts = list(range(0, n - shard_bars - 1, shard_bars))
        last = n - shard_bars - 1
        if not starts or starts[-1] != last:
            starts.append(last)
        self.starts = starts

        self.tape = None
        self._decoder = None
        self.ring_shards = 2  # uncompressed: the double buffer
        if self.compress == "off":
            self.host_data = host_data
            return
        import jax

        tape = C.encode_market_data(
            host_data, starts=starts, shard_bars=shard_bars,
            window_size=self.window_size, tick_size=tick_size, what=what,
        )
        ring_bytes = budget_bytes - 2.0 * tape.decoded_shard_nbytes
        ring = int(ring_bytes // max(1, tape.shard_nbytes))
        if ring < 2:
            raise ValueError(
                f"stream_hbm_budget_mb={budget_mb} cannot hold two "
                f"decoded shards ({2 * tape.decoded_shard_nbytes / 2**20:.1f}"
                " MiB) plus two compressed shards "
                f"({tape.shard_nbytes / 2**20:.2f} MiB each, "
                f"{tape.nbytes / 2**20:.1f} MiB total compressed) — raise "
                "the budget or set data_compress=off"
            )
        self.ring_shards = min(ring, len(starts))
        # full compressed tape fits the ring: park it on device once and
        # decode shards from resident slabs (no steady-state host DMA);
        # otherwise stream the (4x smaller) compressed shards from host
        self.tape_resident = ring >= len(starts)
        if self.tape_resident:
            tape = C.device_tape(tape, placement)
        self.tape = tape
        self._decoder = C.make_shard_decoder(tape, self.compress)
        # drop the host f32 reference: compressed mode never holds the
        # full-width tape and its compressed form at the same time
        self.host_data = None

    @property
    def num_shards(self) -> int:
        return len(self.starts)

    @property
    def resident_bars(self) -> int:
        """Bar capacity resident on device under the budget: the ring of
        compressed shards (plus decode buffers) when compressed, the
        double buffer otherwise."""
        return self.ring_shards * self.shard_bars

    @property
    def compression_ratio(self) -> Optional[float]:
        return None if self.tape is None else self.tape.compression_ratio

    def nbytes_report(self) -> Dict[str, Any]:
        """Compressed vs decoded byte accounting (see
        :func:`market_data_nbytes_report`)."""
        return market_data_nbytes_report(self.host_data, self.tape)

    def serve_ranges(self):
        """[(lo, hi_or_None), ...]: shard k serves bar cursors in
        [lo, hi); the final shard serves to the end (hi=None)."""
        out = []
        for k, lo in enumerate(self.starts):
            hi = self.starts[k + 1] if k + 1 < len(self.starts) else None
            out.append((lo, hi))
        return out

    def _device_shard(self, k: int) -> MarketData:
        import jax

        if self.tape is not None:
            from gymfx_tpu.data import compress as C

            arrs = C.shard_arrays(self.tape, k)
            if not self.tape_resident:
                # stream the compressed shard (4x+ smaller DMA), decode
                # on device into the f32 double buffer
                if self.placement is not None:
                    arrs = jax.tree.map(
                        lambda x: jax.device_put(x, self.placement), arrs
                    )
                else:
                    arrs = jax.tree.map(jax.device_put, arrs)
            shard = self._decoder(arrs)
            if self.placement is not None:
                shard = jax.tree.map(
                    lambda x: jax.device_put(x, self.placement), shard
                )
            return shard
        shard = shard_market_data(
            self.host_data, self.starts[k], self.shard_bars, self.window_size
        )
        # device_put on host numpy is async: it enqueues the transfer
        # and returns immediately — the double buffer.
        if self.placement is not None:
            return jax.tree.map(
                lambda x: jax.device_put(x, self.placement), shard
            )
        return jax.tree.map(jax.device_put, shard)

    def iter_shards(self):
        """Yield ``(serve_lo, serve_hi_or_None, device_shard)`` in
        order, with shard ``k+1``'s transfer already enqueued before
        shard ``k`` is handed to the caller for compute."""
        nxt = self._device_shard(0)
        for k in range(len(self.starts)):
            cur = nxt
            if k + 1 < len(self.starts):
                nxt = self._device_shard(k + 1)
            hi = self.starts[k + 1] if k + 1 < len(self.starts) else None
            yield self.starts[k], hi, cur


def load_dataframe(config: Dict[str, Any]) -> pd.DataFrame:
    """CSV -> dataframe with datetime index and OHLCV backfill.

    Canonical bar files (exactly the DATE_TIME,OHLCV schema) go through
    the native C++ columnar parser when it is available; anything else —
    extra feature columns, custom date column, headerless files — takes
    the pandas path with identical semantics."""
    file_path = config.get("input_data_file")
    if not file_path:
        raise ValueError("config key 'input_data_file' is required")
    headers = bool(config.get("headers", True))
    max_rows = config.get("max_rows")

    if (
        headers
        and max_rows is None  # pandas' nrows stops early; native would not
        and str(config.get("date_column", "DATE_TIME")) == "DATE_TIME"
        and str(config.get("price_column", "CLOSE")) == "CLOSE"
    ):
        from gymfx_tpu.data.native_loader import load_ohlcv_csv

        native = load_ohlcv_csv(str(file_path))
        if native is not None:
            return native

    df = pd.read_csv(file_path, header=0 if headers else None, nrows=max_rows)

    date_col = str(config.get("date_column", "DATE_TIME"))
    if date_col in df.columns:
        df[date_col] = pd.to_datetime(df[date_col], errors="coerce")
        df = df.dropna(subset=[date_col]).set_index(date_col)

    price_col = str(config.get("price_column", "CLOSE"))
    if price_col not in df.columns:
        raise ValueError(f"price_column '{price_col}' not found in data")
    for column in OHLC_COLUMNS:
        if column not in df.columns:
            df[column] = df[price_col]
    if "VOLUME" not in df.columns:
        df["VOLUME"] = 0
    return df


def load_market_dataset(config: Dict[str, Any]) -> MarketDataset:
    return MarketDataset(load_dataframe(config), config)
