"""The tokens of a served window, built by the benchmark from the tape alone:
what a request ending at bar ``b`` should carry, for the plain reference and
for the comparison with what the program encoded.

A window of ``window`` bars ends at bar ``b`` (bars ``b - window + 1 .. b``, the
first bar repeated before the tape's start).  Each of the ``columns`` is
z-scored against the moments of the ``zscore_window`` rows up to and
including bar ``b`` (fewer at the tape's head; under two rows: 0; a deviation
under 1e-8 counts as 1), clipped to +-``clip``.  A window opens a session, so
the agent holds nothing: its four scalars are the equity change 0, the
position 0, the unrealised result 0 and the share of the tape left after bar
``b``, ``(n - b - 1) / n``.  A token is, in this order: the equity change, the
``columns``' z-scores, the position, the share left, the unrealised result
(the scalars repeated along the window).  Computed in float64, returned
float32.
"""
from __future__ import annotations

import numpy as np


def load_columns(tape_path, columns) -> np.ndarray:
    """The tape's ``columns`` (n, len(columns)) in float64."""
    import pandas as pd

    return pd.read_csv(tape_path, usecols=list(columns))[list(columns)].to_numpy(np.float64)


def tokens_at(values: np.ndarray, bars, window: int, zscore_window: int,
              clip: float) -> np.ndarray:
    """(len(bars), window, columns + 4) tokens of the windows ending at ``bars``."""
    n, f = values.shape
    bars = np.asarray(bars, np.int64)
    out = np.zeros((len(bars), window, f + 4), np.float64)
    for i, b in enumerate(bars):
        rows = np.clip(np.arange(b - window + 1, b + 1), 0, None)
        past = values[max(0, b + 1 - zscore_window):b + 1]
        if len(past) < 2:
            z = np.zeros((window, f))
        else:
            std = past.std(axis=0)
            std = np.where(std < 1e-8, 1.0, std)
            z = np.clip((values[rows] - past.mean(axis=0)) / std, -clip, clip)
        out[i, :, 1:1 + f] = z
        out[i, :, f + 2] = (n - b - 1) / n
    return out.astype(np.float32)
