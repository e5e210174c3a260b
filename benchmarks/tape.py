"""The benchmark's bar tapes, generated from a fixed seed.

A COPY of ``tools/make_example_data.make_m1_quarter`` (the three-month M1
series: AR(1) momentum in log-returns plus an intraday seasonal drift),
kept here so that a later PR to ``tools/`` cannot change the tape the
cells are measured on.  A traffic file names the generator and its
parameters; the CSV is written once per checkout under ``.bench_data/``
(git-ignored) and found again by every later run.
"""
from __future__ import annotations

import os
from pathlib import Path

DATA_DIR = Path(__file__).resolve().parent.parent / ".bench_data"


def m1_quarter(bars: int = 132_480, seed: int = 20260701, phi: float = 0.35,
               sigma: float = 5e-5, season_amp: float = 1.2e-5):
    import numpy as np
    import pandas as pd

    n = int(bars)
    rng = np.random.default_rng(seed)
    ts = pd.date_range("2026-01-05 00:00:00", periods=n, freq="1min")
    eps = rng.normal(0.0, sigma, n)
    r = np.empty(n)
    r[0] = eps[0]
    for t in range(1, n):
        r[t] = phi * r[t - 1] + eps[t]
    minute_of_day = ts.hour.to_numpy() * 60 + ts.minute.to_numpy()
    drift = season_amp * np.sin(2.0 * np.pi * minute_of_day / 1440.0)
    close = np.round(np.exp(np.log(1.10) + np.cumsum(r + drift)), 5)
    open_ = np.empty(n)
    open_[0] = 1.10
    open_[1:] = close[:-1]
    wick = np.abs(rng.normal(0.0, sigma, n)) * close
    high = np.round(np.maximum(open_, close) + wick, 5)
    low = np.round(np.minimum(open_, close) - wick, 5)
    ret1 = np.zeros(n)
    ret1[1:] = np.diff(np.log(close))
    ret5 = np.zeros(n)
    ret5[5:] = np.log(close[5:]) - np.log(close[:-5])
    return pd.DataFrame({
        "DATE_TIME": ts.strftime("%Y-%m-%d %H:%M:%S"),
        "OPEN": np.round(open_, 5), "HIGH": high, "LOW": low, "CLOSE": close,
        "VOLUME": rng.integers(50, 2000, n), "RET1": ret1, "RET5": ret5,
    })


GENERATORS = {"m1_quarter": m1_quarter}


def ensure_tape(spec: dict) -> Path:
    """Write the tape a traffic file describes (``{"generator", ...its
    parameters}``) if this checkout does not hold it yet; return its path.
    The name carries every parameter, so two mixes never share a file by
    accident; the write is atomic (rename), so a cut run leaves no half."""
    params = {k: v for k, v in spec.items() if k != "generator"}
    tag = "_".join(f"{k}{params[k]}" for k in sorted(params))
    out = DATA_DIR / f"{spec['generator']}_{tag}.csv"
    if not out.exists():
        DATA_DIR.mkdir(parents=True, exist_ok=True)
        tmp = out.with_suffix(f".tmp{os.getpid()}")
        GENERATORS[spec["generator"]](**params).to_csv(tmp, index=False)
        os.replace(tmp, out)
    return out
