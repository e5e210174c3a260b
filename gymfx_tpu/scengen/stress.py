"""Structured market stress for the chaos harness.

The ``fault_profile`` grammar (resilience/faults.py) gains a
``scengen=<preset>`` clause: instead of synthesizing a whole tape, this
overlays the preset's stress machinery — flash-crash drops with recovery
tails, liquidity-drought spread blowouts, gap level shifts — onto an
EXISTING MarketData, so chaos runs fuzz trainers with structured market
moves on top of the bars they were already consuming (the same
_replace-and-rebuild host path as contaminate_market_data).

Deterministic: the event layout is drawn from ``np.random.default_rng``
on the profile's seed, and each stress family fires AT LEAST once when
the preset enables it (a chaos run must never silently reduce to the
clean baseline because the draw came up empty).
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from gymfx_tpu.data.feed import pack_bars

from .params import (
    FLAG_CRASH,
    FLAG_DROUGHT,
    FLAG_GAP,
    scenario_params,
)


def _event_starts(
    rng: np.random.Generator, n: int, rate: float, width: int,
    at_least_one: bool,
) -> np.ndarray:
    """Non-overlapping window starts drawn at ``rate`` per bar."""
    if rate <= 0 and not at_least_one:
        return np.zeros(0, np.int64)
    count = int(rng.binomial(max(n - width, 1), max(rate, 0.0)))
    if at_least_one:
        count = max(count, 1)
    hi = max(n - width, 1)
    starts = np.sort(rng.integers(0, hi, size=count))
    picked = []
    last_end = -1
    for s in starts:
        if s > last_end:
            picked.append(int(s))
            last_end = int(s) + width
    return np.asarray(picked, np.int64)


def apply_scengen_stress(
    data: Any, preset: str, seed: int = 0
) -> Any:
    """Overlay the preset's stress events onto ``data`` and return the
    rebuilt MarketData (prices scaled multiplicatively, padded_close
    mirrored, event spread/slippage multipliers compounded, scen_flags
    bits set)."""
    import jax.numpy as jnp

    p = scenario_params(preset)
    rng = np.random.default_rng(int(seed))
    close = np.asarray(data.close)
    n = int(close.shape[0])

    # per-bar log-price deltas accumulate into a level-shift curve
    delta = np.zeros(n, np.float64)
    spread_mult = np.ones(n, np.float64)
    flags = np.zeros(n, np.int32)

    crash_len = max(int(p.crash_len), 1)
    recovery_len = max(int(p.recovery_len), 1)
    # a family is enabled by its RATE (crash_size is a magnitude with a
    # nonzero default on every preset, so it must not gate the family)
    if float(p.p_crash) > 0:
        width = crash_len + recovery_len
        for s in _event_starts(rng, n, float(p.p_crash), width, True):
            drop = float(p.crash_size) / crash_len
            gain = float(p.crash_size) * float(p.recovery_frac) / recovery_len
            d_end = min(s + crash_len, n)
            r_end = min(d_end + recovery_len, n)
            delta[s:d_end] -= drop
            delta[d_end:r_end] += gain
            spread_mult[s:d_end] *= float(p.crash_spread)
            flags[s:d_end] |= FLAG_CRASH

    if float(p.p_drought) > 0:
        width = max(int(p.drought_len), 1)
        for s in _event_starts(rng, n, float(p.p_drought), width, True):
            end = min(s + width, n)
            spread_mult[s:end] *= float(p.drought_spread)
            flags[s:end] |= FLAG_DROUGHT

    if float(p.p_gap) > 0:
        for b in _event_starts(rng, n, float(p.p_gap), 1, True):
            delta[b] += float(rng.normal(0.0, float(p.gap_size)))
            flags[b] |= FLAG_GAP

    factor = np.exp(np.cumsum(delta))

    replace: Dict[str, Any] = {}
    for field in ("open", "high", "low", "close"):
        arr = np.asarray(getattr(data, field)) * factor
        replace[field] = jnp.asarray(arr, dtype=getattr(data, field).dtype)
    padded = np.asarray(data.padded_close).copy()
    pad = padded.shape[0] - n
    padded[pad:] = padded[pad:] * factor
    replace["padded_close"] = jnp.asarray(padded, data.padded_close.dtype)

    ev_spread = np.asarray(data.ev_spread_mult) * spread_mult
    ev_slip = np.asarray(data.ev_slip_mult) * (
        1.0 + 0.5 * (spread_mult - 1.0)
    )
    replace["ev_spread_mult"] = jnp.asarray(ev_spread, np.float32)
    replace["ev_slip_mult"] = jnp.asarray(ev_slip, np.float32)

    prev = np.asarray(data.scen_flags)
    if prev.shape != flags.shape:  # replay feeds carry the scalar 0
        prev = np.zeros(n, np.int32)
    replace["scen_flags"] = jnp.asarray(prev | flags, jnp.int32)
    return pack_bars(data._replace(**replace))
