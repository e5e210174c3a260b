"""Multi-pair portfolio environment (BASELINE.json config 5).

New capability: the reference env trades a single instrument; its only
multi-asset surface is the Nautilus replay fixture
(reference simulation_engines/bakeoff.py:26-101, margin preflight with
cross-currency conversion nautilus_adapter.py:191-237).  Here the
portfolio env is the single-pair kernel itself, ``jax.vmap``-ed over an
instrument axis — NOT a simplified sibling:

  * each pair advances through the REAL ``core.env.step`` (pending
    fills at next open, bracket SL/TP against the bar's H/L under the
    profile's collision + limit-fill policies, ATR strategy with
    session/weekend filter, event-context overlay, rollover financing,
    full diagnostics) with its own quote-currency ledger and its own
    ``EnvParams`` — per-pair execution-cost profiles are just different
    rows of the stacked params pytree;
  * one shared account couples the pairs: per-bar quote->account
    conversion factors (direct pairs convert by rule, crosses bridge
    through another pair in the book — same rule as the reconciliation
    oracle, simulation/oracle.py), account-level margin preflight over
    the opening margin of ALL newly-submitted orders (greedy in pair
    order, deterministic), account-level reward kernels
    (pnl/sharpe/dd with the explicit carries of core/rewards.py), the
    stage-B force-close penalty, and account-level bankruptcy
    termination.

Accounting note: each pair's ledger lives in its quote currency and is
converted at the CURRENT bar's rate when the account is marked, so
realized pnl "parked" in a foreign quote currency floats with FX until
the episode ends — how a real multi-currency margin account behaves
before sweeps.  The replay engine (like Nautilus) converts realized pnl
at fill time; the difference is conversion drift on already-realized
pnl.  ``sweep_realized_pnl: true`` switches the account to the
replay/fill-time semantics: each bar's realized increment is banked in
the account currency at that bar's rate, bounding the residual to one
bar's FX move on the increment (tests/test_portfolio.py drift tests);
the default keeps the float-with-FX behavior the oracle reconciles,
whose drift is exactly sum(realized_q * (conv_now - conv_then)) — see
DIVERGENCES.md.

Static-policy constraint: per-pair profiles may differ in every numeric
field (commission, spread, slippage, margin), but fields that select
compiled code paths (collision policy, limit-fill policy, margin model,
financing) must agree across pairs — one XLA program serves all pairs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from gymfx_tpu.core import broker
from gymfx_tpu.core import env as env_core
from gymfx_tpu.core import rewards
from gymfx_tpu.core.types import (
    EXEC_DIAG_INDEX,
    EnvConfig,
    EnvParams,
    EnvState,
    initial_state,
    make_env_config,
    make_env_params,
)
from gymfx_tpu.data.feed import MarketData, MarketDataset


class PortfolioData(NamedTuple):
    pair: MarketData   # every leaf stacked with a leading (I,) axis
    conv: Any          # (n, I) quote->account conversion factor

    @property
    def n_bars(self) -> int:
        return int(self.pair.close.shape[1])

    @property
    def n_pairs(self) -> int:
        return int(self.pair.close.shape[0])

    # (n, I) convenience views matching the portfolio layout
    @property
    def open(self):
        return self.pair.open.T

    @property
    def high(self):
        return self.pair.high.T

    @property
    def low(self):
        return self.pair.low.T

    @property
    def close(self):
        return self.pair.close.T


@dataclasses.dataclass(frozen=True)
class PortfolioConfig:
    n_pairs: int
    n_bars: int
    window_size: int
    pair_cfg: EnvConfig    # inner per-pair kernel config
    acct_cfg: EnvConfig    # account-level reward/penalty config
    enforce_margin_preflight: bool = False
    enforce_margin_closeout: bool = False
    margin_model: str = "leveraged"
    # opt-in (``sweep_realized_pnl``): convert each bar's REALIZED pnl
    # increment to the account currency at that bar's rate and bank it,
    # instead of letting realized pnl float in the quote currency until
    # episode end — the replay/Nautilus fill-time conversion semantics
    # (bounded residual: one bar's FX move on the increment, vs the
    # whole episode's move on the balance).  Default off = the
    # real-margin-account behavior the oracle reconciles.
    sweep_realized_pnl: bool = False
    dtype: Any = jnp.float32


class PortfolioParams(NamedTuple):
    pair: EnvParams        # every leaf (I,); margin_init is per-pair here
    acct: EnvParams        # scalars (account currency)


class PortfolioState(NamedTuple):
    pairs: EnvState        # every leaf with a leading (I,) axis
    acct: EnvState         # scalar account-level carry
    # realized-pnl sweep carries (used when cfg.sweep_realized_pnl; zero
    # otherwise): account-currency bank of swept realized pnl, and each
    # pair's last-seen realized balance delta (quote currency)
    swept_realized: Any = 0.0      # scalar, account currency
    prev_realized_q: Any = 0.0     # (I,) quote currency


# ---------------------------------------------------------------------------
# host-side data loading
# ---------------------------------------------------------------------------
def load_portfolio_frames(
    files: Dict[str, str],
    *,
    date_column: str = "DATE_TIME",
    price_column: str = "CLOSE",
    max_rows: Optional[int] = None,
) -> Tuple[List[str], Dict[str, pd.DataFrame]]:
    """Load and time-align several pair CSVs on their shared timestamps
    (inner join).  Returns (pair names, per-pair aligned frames)."""
    frames: Dict[str, pd.DataFrame] = {}
    for pair, path in files.items():
        df = pd.read_csv(path, nrows=max_rows)
        df[date_column] = pd.to_datetime(df[date_column], errors="coerce")
        df = df.dropna(subset=[date_column]).set_index(date_column)
        for col in ("OPEN", "HIGH", "LOW", "CLOSE"):
            if col not in df.columns:
                df[col] = df[price_column]
        frames[pair] = df
    common = None
    for df in frames.values():
        common = df.index if common is None else common.intersection(df.index)
    if common is None or len(common) < 3:
        raise ValueError("portfolio pairs share too few timestamps")
    aligned = {pair: df.loc[common] for pair, df in frames.items()}
    return list(files.keys()), aligned


def build_conversion_factors(
    pairs: Sequence[str],
    closes: np.ndarray,          # (n, I) float64
    account_currency: str = "USD",
) -> np.ndarray:
    """(n, I) quote-currency -> account-currency factors; crosses bridge
    through another pair in the book that quotes/bases the account
    currency (same direct-pair rule as the reconciliation oracle)."""
    n = closes.shape[0]
    parsed = [p.replace("/", "_").split("_", 1) for p in pairs]
    conv = np.ones((n, len(pairs)))
    for i, (base, quote) in enumerate(parsed):
        if quote == account_currency:
            conv[:, i] = 1.0
        elif base == account_currency:
            conv[:, i] = 1.0 / closes[:, i]
        else:
            bridge = None
            for j, (b2, q2) in enumerate(parsed):
                if b2 == quote and q2 == account_currency:
                    bridge = closes[:, j]          # quote/ACC price
                    break
                if b2 == account_currency and q2 == quote:
                    bridge = 1.0 / closes[:, j]    # ACC/quote price inverted
                    break
            if bridge is None:
                raise ValueError(
                    f"pair {pairs[i]}: no direct conversion from {quote} to "
                    f"{account_currency} and no bridging pair in the book"
                )
            conv[:, i] = bridge
    return conv


# ---------------------------------------------------------------------------
# pure kernel: reset / step
# ---------------------------------------------------------------------------
def reset(cfg: PortfolioConfig, params: PortfolioParams, data: PortfolioData):
    pair_reset = lambda p, d: env_core.reset(cfg.pair_cfg, p, d)  # noqa: E731
    pairs, obs_i = jax.vmap(pair_reset)(params.pair, data.pair)
    acct = initial_state(cfg.acct_cfg)
    eq = jnp.sum(data.conv[0] * pairs.equity_delta).astype(acct.equity_delta.dtype)
    acct = acct._replace(
        equity_delta=eq,
        prev_equity_delta=eq,
        peak_equity_delta=jnp.maximum(acct.peak_equity_delta, eq),
    )
    state = PortfolioState(
        pairs=pairs, acct=acct,
        swept_realized=jnp.zeros((), cfg.dtype),
        prev_realized_q=jnp.zeros((cfg.n_pairs,), cfg.dtype),
    )
    return state, _portfolio_obs(obs_i, state, data, cfg, params)


def step(cfg: PortfolioConfig, params: PortfolioParams, data: PortfolioData,
         state: PortfolioState, actions):
    """actions: (I,) ints in {0=hold, 1=long, 2=short, 3=flat}."""
    was_terminated = state.acct.terminated
    live = ~was_terminated

    # terminated account -> per-pair steps become no-ops (their own
    # terminated flags were set when the account terminated)
    pair_step = lambda p, d, s, a: env_core.step(  # noqa: E731
        cfg.pair_cfg, p, d, s, a
    )
    pairs, obs_i, _pr, _pd, info_i = jax.vmap(pair_step)(
        params.pair, data.pair, state.pairs,
        jnp.asarray(actions, jnp.int32).reshape(cfg.n_pairs),
    )

    t_new = pairs.t[0]
    conv = data.conv[t_new]                        # (I,)
    close = data.pair.close[jnp.arange(cfg.n_pairs), t_new]  # (I,)

    # ---- account-level margin preflight over newly-submitted orders ----
    # (the inner kernel's own preflight is disabled; the account gate
    # sees the whole book).  Greedy in pair order: each order is granted
    # only if the margin GRANTED so far plus its own still fits the free
    # realized balance — denied orders reserve nothing, matching a
    # sequential broker (and the replay engine) processing one order at
    # a time.  Deterministic regardless of XLA scheduling.
    if cfg.enforce_margin_preflight:
        opening = broker.opening_units(pairs.pos, pairs.pending_target)  # (I,)
        required_q = opening * close * params.pair.margin_init
        if cfg.margin_model == "leveraged":
            required_q = required_q / jnp.maximum(params.pair.leverage, 1e-12)
        required = required_q * conv               # account currency
        if cfg.sweep_realized_pnl:
            # fill-time-conversion mode: free balance = banked realized
            # pnl (historic rates) + this bar's unbanked increment at the
            # current rate — the same measure the equity mark below uses,
            # so margin granted never diverges from the account's equity
            realized_q = pairs.cash_delta + pairs.pos * pairs.entry_price
            free = (
                params.acct.initial_cash
                + state.swept_realized
                + jnp.sum(conv * (realized_q - state.prev_realized_q))
            )
        else:
            free = params.acct.initial_cash + jnp.sum(
                conv * (pairs.cash_delta + pairs.pos * pairs.entry_price)
            )
        want = pairs.pending_active & (opening > 0)

        def grant_body(granted_sum, req_want):
            req, wants = req_want
            ok = wants & (granted_sum + req <= free)
            return granted_sum + jnp.where(ok, req, 0.0), ok

        _, granted = jax.lax.scan(
            grant_body, jnp.zeros_like(free), (required, want)
        )
        denied = want & ~granted
        pairs = pairs._replace(
            pending_active=pairs.pending_active & ~denied,
            pending_target=jnp.where(denied, 0.0, pairs.pending_target),
            pending_sl=jnp.where(denied, 0.0, pairs.pending_sl),
            pending_tp=jnp.where(denied, 0.0, pairs.pending_tp),
            exec_diag=pairs.exec_diag.at[:, EXEC_DIAG_INDEX["preflight_denied"]].add(
                denied.astype(jnp.int32)
            ),
        )

    # ---- account equity mark + reward ---------------------------------
    acct = state.acct
    n = cfg.n_bars
    advance = live & acct.started & (acct.t < n - 1)
    exhausted = live & acct.started & (acct.t >= n - 1)
    marking = advance | (live & ~acct.started)

    if cfg.sweep_realized_pnl:
        # fill-time conversion semantics (replay/Nautilus): each bar's
        # realized increment is banked at THAT bar's rate; only the
        # unrealized leg floats with FX.  realized_q = cash + pos*entry
        # (the position's entry notional cancels the open cash outlay),
        # unrealized_q = pos * (close - entry).
        realized_q = (pairs.cash_delta + pairs.pos * pairs.entry_price).astype(
            state.prev_realized_q.dtype
        )
        unrealized_q = pairs.equity_delta - realized_q
        swept = state.swept_realized + jnp.sum(
            conv * (realized_q - state.prev_realized_q)
        ).astype(state.swept_realized.dtype)
        swept = jnp.where(marking, swept, state.swept_realized)
        prev_realized_q = jnp.where(
            marking, realized_q, state.prev_realized_q
        )
        eq = (swept + jnp.sum(conv * unrealized_q)).astype(
            acct.equity_delta.dtype
        )
    else:
        swept = state.swept_realized
        prev_realized_q = state.prev_realized_q
        eq = jnp.sum(conv * pairs.equity_delta).astype(acct.equity_delta.dtype)
    acct = acct._replace(
        t=t_new,
        started=acct.started | live,
        prev_equity_delta=jnp.where(marking, acct.equity_delta, acct.prev_equity_delta),
        equity_delta=jnp.where(marking, eq, acct.equity_delta),
        pos=jnp.sum(jnp.abs(pairs.pos)).astype(acct.pos.dtype),
    )
    peak = jnp.where(marking, jnp.maximum(acct.peak_equity_delta, acct.equity_delta),
                     acct.peak_equity_delta)
    money_down = peak - acct.equity_delta
    peak_equity = params.acct.initial_cash + peak
    acct = acct._replace(
        peak_equity_delta=peak,
        max_drawdown_money=jnp.maximum(acct.max_drawdown_money, money_down),
        max_drawdown_pct=jnp.maximum(
            acct.max_drawdown_pct,
            jnp.where(peak_equity > 0, money_down / peak_equity * 100.0, 0.0),
        ),
    )

    # ---- account maintenance-margin closeout ---------------------------
    # equity marked below the book's total maintenance requirement
    # force-flattens EVERY pair at the next bar's open (deterministic
    # whole-book liquidation; OANDA-style partial closeouts would be
    # order-dependent).  Forced flats REPLACE any pending orders.
    if cfg.enforce_margin_closeout:
        maint = jnp.sum(
            broker.maintenance_margin(pairs.pos, close, params.pair,
                                      cfg.margin_model) * conv
        )
        equity_now = params.acct.initial_cash + acct.equity_delta
        # gated on `advance` like the single-pair kernel (core/env.py
        # step 4b): the exhausted step would double-count the breach
        breach = advance & jnp.any(pairs.pos != 0) & (equity_now < maint)
        held = breach & (pairs.pos != 0)
        pairs = pairs._replace(
            pending_active=jnp.where(breach, pairs.pos != 0, pairs.pending_active),
            pending_target=jnp.where(breach, 0.0, pairs.pending_target),
            pending_sl=jnp.where(breach, 0.0, pairs.pending_sl),
            pending_tp=jnp.where(breach, 0.0, pairs.pending_tp),
            pending_forced=pairs.pending_forced | held,
            exec_diag=pairs.exec_diag.at[:, EXEC_DIAG_INDEX["margin_closeouts"]].add(
                held.astype(jnp.int32)
            ),
        )

    acct, base_reward = rewards.compute_reward(acct, cfg.acct_cfg, params.acct, live)
    fc_row = jnp.minimum(t_new + 1, n - 1)
    penalty = rewards.force_close_penalty(
        acct, data.pair.force_close[0, fc_row], cfg.acct_cfg, params.acct
    )
    penalty = jnp.where(live, penalty, 0.0)
    reward = base_reward - penalty

    # ---- account termination ------------------------------------------
    equity = params.acct.initial_cash + acct.equity_delta
    broke = equity <= params.acct.min_equity
    terminated = was_terminated | exhausted | (live & broke)
    from gymfx_tpu.core.types import TERMINATION_BANKRUPT, TERMINATION_EXHAUSTED

    reason_now = jnp.where(
        live & broke,
        jnp.int32(TERMINATION_BANKRUPT),
        jnp.where(exhausted, jnp.int32(TERMINATION_EXHAUSTED), jnp.int32(0)),
    )
    acct = acct._replace(
        terminated=terminated,
        termination_reason=jnp.where(
            was_terminated, acct.termination_reason, reason_now
        ).astype(jnp.int32),
    )
    pairs = pairs._replace(terminated=pairs.terminated | terminated)

    new_state = PortfolioState(
        pairs=pairs, acct=acct,
        swept_realized=swept, prev_realized_q=prev_realized_q,
    )
    obs = _portfolio_obs(obs_i, new_state, data, cfg, params)
    info = _portfolio_info(info_i, new_state, conv, cfg, params)
    info["reward"] = reward
    info["force_close_reward_penalty"] = penalty
    return new_state, obs, reward, terminated, info


def _portfolio_obs(obs_i: Dict[str, Any], state: PortfolioState,
                   data: PortfolioData, cfg: PortfolioConfig,
                   params: PortfolioParams) -> Dict[str, Any]:
    """Vmapped per-pair obs blocks -> portfolio layout: window blocks are
    (window, I) (bars as the leading axis, pairs as channels), per-pair
    scalars are (I,), account scalars are (1,)."""
    obs: Dict[str, Any] = {}
    if "features" in obs_i:
        f = obs_i["features"]                  # (I, w, F)
        obs["features"] = jnp.transpose(f, (1, 0, 2)).reshape(
            f.shape[1], -1
        )
    if "prices" in obs_i:
        obs["prices"] = obs_i["prices"].T      # (w, I)
        obs["returns"] = obs_i["returns"].T
    if "position" in obs_i:
        obs["position"] = obs_i["position"][:, 0]  # (I,)
        obs["unrealized_pnl_norm"] = obs_i["unrealized_pnl_norm"][:, 0]
    initial = jnp.where(params.acct.initial_cash == 0, 1.0, params.acct.initial_cash)
    obs["equity_norm"] = jnp.asarray(
        [state.acct.equity_delta / initial], jnp.float32
    )
    obs["steps_remaining_norm"] = jnp.asarray(
        [jnp.maximum(0, cfg.n_bars - (state.acct.t + 1)) / max(1, cfg.n_bars)],
        jnp.float32,
    )
    # shared-timestamp blocks (stage-B / calendar) are identical across
    # pairs, so pair 0's copy is surfaced; that collapse is applied ONLY
    # to the known timestamp-derived keys — anything else (a registered
    # obs kernel's block may be per-pair state) keeps its full (I, ...)
    # array.  Account-DEPENDENT calendar entries are excluded and
    # re-emitted from the account ledger below — pair 0's quote-currency
    # view would be wrong for the book.
    from gymfx_tpu.data.calendar import FORCE_CLOSE_FEATURE_KEYS
    from gymfx_tpu.core.obs import CALENDAR_OBS_KEYS

    account_dependent = ("margin_available_norm", "margin_closeout_percent")
    shared_keys = set(FORCE_CLOSE_FEATURE_KEYS) | set(CALENDAR_OBS_KEYS)
    handled = {
        "position", "unrealized_pnl_norm", "equity_norm",
        "steps_remaining_norm", *account_dependent,
    }
    for key, val in obs_i.items():
        if key in obs or key in handled:
            continue
        obs[key] = val[0] if key in shared_keys else val
    if "margin_available_norm" in obs_i:
        # account-level margin ratio from the real book: total
        # maintenance requirement over account equity (1.0 = liquidation
        # boundary), mirroring the single-pair ledger value
        # (core/broker.py margin_closeout_percent)
        t = state.acct.t
        close = data.pair.close[jnp.arange(cfg.n_pairs), t]
        conv = data.conv[t]
        maint = jnp.sum(
            broker.maintenance_margin(state.pairs.pos, close, params.pair,
                                      cfg.margin_model) * conv
        )
        equity = params.acct.initial_cash + state.acct.equity_delta
        pct = jnp.where(equity > 0, maint / jnp.maximum(equity, 1e-30), 100.0)
        pct = jnp.where(jnp.any(state.pairs.pos != 0), pct, 0.0)
        obs["margin_closeout_percent"] = jnp.clip(pct, 0.0, 100.0)[None].astype(
            jnp.float32
        )
        obs["margin_available_norm"] = jnp.asarray(
            [(params.acct.initial_cash + state.acct.equity_delta) / initial],
            jnp.float32,
        )
    return obs


def _portfolio_info(info_i: Dict[str, Any], state: PortfolioState, conv,
                    cfg: PortfolioConfig, params: PortfolioParams) -> Dict[str, Any]:
    pairs = state.pairs
    equity = params.acct.initial_cash + state.acct.equity_delta
    info = {
        "equity": equity,
        "equity_delta": state.acct.equity_delta,
        "positions": jnp.sign(pairs.pos).astype(jnp.int32),
        "position_units": pairs.pos,
        "bar_index": state.acct.t + 1,
        "trades": jnp.sum(pairs.trade_count).astype(jnp.int32),
        "commission_paid": jnp.sum(conv * pairs.commission_paid),
        "blocked_margin": jnp.sum(
            pairs.exec_diag[:, EXEC_DIAG_INDEX["preflight_denied"]]
        ).astype(jnp.int32),
        "margin_closeouts": jnp.sum(
            pairs.exec_diag[:, EXEC_DIAG_INDEX["margin_closeouts"]]
        ).astype(jnp.int32),
        "bracket_sl": pairs.bracket_sl,
        "bracket_tp": pairs.bracket_tp,
        "pending_active": pairs.pending_active,
        "atr": info_i["atr"],
        "max_drawdown_money": state.acct.max_drawdown_money,
        "max_drawdown_pct": state.acct.max_drawdown_pct,
        "trades_won": jnp.sum(pairs.trades_won).astype(jnp.int32),
        "trades_lost": jnp.sum(pairs.trades_lost).astype(jnp.int32),
    }
    return info


# ---------------------------------------------------------------------------
# host-side binding
# ---------------------------------------------------------------------------
_STATIC_PROFILE_FIELDS = (
    "intrabar_collision_policy",
    "limit_fill_policy",
    "margin_model",
    "financing_enabled",
    "enforce_margin_preflight",
)


class PortfolioEnvironment:
    """Host-side binding: pair CSVs -> jitted portfolio reset/step."""

    def __init__(self, config: Dict[str, Any],
                 split: Optional[Tuple[str, float]] = None):
        """``split=("train"|"eval", frac)`` applies the chronological
        out-of-sample split AFTER the cross-pair timestamp join: the
        last ``frac`` of the ALIGNED bars is the eval part, so the two
        parts never share a bar on any pair (train/common.py
        build_portfolio_train_eval_envs)."""
        self.config = dict(config)
        account = str(config.get("account_currency", "USD"))
        feed = str(config.get("feed") or "replay").lower()
        from gymfx_tpu.data.compress import validate_compress_mode

        # honor-or-reject: the int16 wire format (data/compress.py)
        # covers single-pair MarketData tapes; portfolio books are
        # PortfolioData pytrees (stacked pair leaves + a conversion
        # matrix) with no compressed form yet
        if validate_compress_mode(config.get("data_compress", "off")) != "off":
            raise ValueError(
                "data_compress applies to single-pair MarketData tapes; "
                "portfolio books (stacked pair leaves + a conversion "
                "matrix) have no compressed form — unset data_compress "
                "for the portfolio env"
            )
        self.curriculum = None
        curriculum_specs = None
        base_config = None
        if feed == "curriculum":
            from gymfx_tpu.data import tapes as tapes_mod

            if split is not None:
                raise ValueError(
                    "feed=curriculum cannot be combined with eval_split "
                    "on the portfolio env (which tape would be cut?); "
                    "evaluate on a held-out book instead"
                )
            curriculum_specs = tapes_mod.parse_tape_specs(config)
            base_config = dict(config)
            # rebind this env to tape 0 — the overlay strips the
            # curriculum keys, so the nested tape builds cannot recurse
            config = tapes_mod.overlay_config(config, curriculum_specs[0])
            self.config = dict(config)
            feed = str(config.get("feed") or "replay").lower()
        if feed == "scengen":
            # correlated multi-asset generation on one shared grid —
            # already aligned, no timestamp join needed
            from gymfx_tpu.scengen.feed import synthesize_portfolio_frames

            pairs, aligned, _flags = synthesize_portfolio_frames(config)
        else:
            files = config.get("portfolio_files")
            if not files:
                raise ValueError(
                    "portfolio env requires config['portfolio_files'] "
                    "(or feed=scengen for a generated book)"
                )
            pairs, aligned = load_portfolio_frames(
                dict(files),
                date_column=str(config.get("date_column", "DATE_TIME")),
                price_column=str(config.get("price_column", "CLOSE")),
                max_rows=config.get("max_rows"),
            )
        self.pairs = pairs
        w = int(config.get("window_size", 32))
        if split is not None:
            part, frac = split
            frac = float(frac)
            if part not in ("train", "eval"):
                raise ValueError(f"split part must be train|eval, got {part!r}")
            if not 0.0 < frac < 1.0:
                raise ValueError(f"eval_split must be in (0, 1), got {frac!r}")
            n_all = len(next(iter(aligned.values())))
            cut = n_all - int(n_all * frac)
            min_bars = w + 2
            if cut < min_bars or n_all - cut < min_bars:
                raise ValueError(
                    f"eval_split={frac} leaves too few aligned bars (train "
                    f"{cut}, eval {n_all - cut}; both need >= {min_bars})"
                )
            sl = slice(0, cut) if part == "train" else slice(cut, None)
            aligned = {p: df.iloc[sl] for p, df in aligned.items()}
        self.timestamps = next(iter(aligned.values())).index
        n = len(next(iter(aligned.values())))
        if n < w + 2:
            raise ValueError("aligned portfolio data too short for the window")

        profiles = self._load_profiles(config, pairs)
        self._check_static_profile_agreement(profiles)
        cfg0 = make_env_config(
            config, n_bars=n, n_features=len(config.get("feature_columns") or []),
            binary_mask=tuple(
                c in set(config.get("feature_binary_columns") or [])
                for c in (config.get("feature_columns") or [])
            ),
            profile=profiles[0],
        )
        # margin backcompat: the old portfolio key 'margin_rate' doubles
        # as margin_init + enforcement flag
        margin_rate = float(config.get("margin_rate", 0.0) or 0.0)
        enforce = bool(cfg0.enforce_margin_preflight or margin_rate > 0)
        enforce_closeout = bool(config.get("enforce_margin_closeout", enforce))
        # the inner kernel runs per-pair with the ACCOUNT-level gates off
        pair_cfg = dataclasses.replace(
            cfg0,
            enforce_margin_preflight=False,
            # margin is an ACCOUNT property: the account-level gates run
            # in portfolio.step; a per-pair closeout on the pair's own
            # quote-currency ledger would double-count the shared cash
            enforce_margin_closeout=False,
            reward="pnl_reward",
            stage_b_force_close_reward_penalty=False,
            allow_flat_action=True,
        )
        acct_cfg = dataclasses.replace(
            cfg0, n_features=0, include_prices=False, include_agent_state=False
        )
        self.cfg = PortfolioConfig(
            n_pairs=len(pairs),
            n_bars=n,
            window_size=w,
            pair_cfg=pair_cfg,
            acct_cfg=acct_cfg,
            enforce_margin_preflight=enforce,
            enforce_margin_closeout=enforce_closeout,
            margin_model=cfg0.margin_model,
            sweep_realized_pnl=bool(config.get("sweep_realized_pnl", False)),
            dtype=cfg0.dtype,
        )

        from gymfx_tpu.core.runtime import (
            load_financing_rates,
            validate_profile_latency,
        )

        financing_rate_data = load_financing_rates(
            config, pair_cfg.financing_enabled
        )

        # per-pair market data through the SAME pipeline as the
        # single-pair env, leaves stacked on a leading pair axis
        datasets = [MarketDataset(aligned[p], config) for p in pairs]
        mds = [
            ds.build_market_data(
                window_size=w,
                feature_columns=tuple(config.get("feature_columns") or ()),
                feature_scaling=str(config.get("feature_scaling", "rolling_zscore")),
                feature_scaling_window=int(config.get("feature_scaling_window", 256)),
                dtype=cfg0.dtype,
                financing_rate_data=financing_rate_data,
                instrument=p,
            )
            for p, ds in zip(pairs, datasets)
        ]
        stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *mds)
        closes = np.stack(
            [aligned[p]["CLOSE"].to_numpy(np.float64) for p in pairs], 1
        )
        conv = build_conversion_factors(pairs, closes, account)
        self.data = PortfolioData(
            pair=stacked, conv=jnp.asarray(conv, cfg0.dtype)
        )

        # per-pair params (per-pair profiles + sizes), stacked to (I,)
        sizes = config.get("portfolio_position_sizes")
        if sizes is None:
            sizes = [float(config.get("position_size", 1.0))] * len(pairs)
        overrides = config.get("portfolio_param_overrides") or {}
        per_pair = []
        for i, p in enumerate(pairs):
            cfg_i = dict(config, position_size=float(sizes[i]), min_equity=None)
            if margin_rate > 0 and "margin_init" not in cfg_i:
                cfg_i["margin_init"] = margin_rate  # legacy portfolio key
            cfg_i.update(overrides.get(p) or {})
            params_i = make_env_params(cfg_i, pair_cfg, profile=profiles[i])
            # pair ledgers never terminate on their own equity: the
            # account gates bankruptcy
            params_i = params_i._replace(
                min_equity=jnp.asarray(-1e30, cfg0.dtype)
            )
            per_pair.append(params_i)
        # tree-map (not per-field zip): EnvParams.user may be a nested
        # pytree of registered-kernel parameters
        pair_params = jax.tree.map(lambda *xs: jnp.stack(xs), *per_pair)
        acct_params = make_env_params(dict(config), acct_cfg, profile=profiles[0])
        self.params = PortfolioParams(pair=pair_params, acct=acct_params)

        # honor-or-reject: latency vs the shared bar interval
        bar_ms = datasets[0].bar_interval_ms()
        for prof in profiles:
            validate_profile_latency(prof, bar_ms)
        self.timeframe_hours = datasets[0].timeframe_hours

        if curriculum_specs is not None:
            from gymfx_tpu.data import tapes as tapes_mod

            self.curriculum = tapes_mod.PortfolioCurriculumSampler(
                base_config, curriculum_specs, base_env=self
            )

    @property
    def n_bars(self) -> int:
        return self.cfg.n_bars

    @staticmethod
    def _load_profiles(config: Dict[str, Any], pairs: List[str]):
        from gymfx_tpu.core.types import _parse_profile

        shared = _parse_profile(config)
        per_pair_raw = config.get("portfolio_profiles") or {}
        profiles = []
        for p in pairs:
            raw = per_pair_raw.get(p)
            if raw is None:
                profiles.append(shared)
            else:
                profiles.append(_parse_profile({"execution_cost_profile": raw}))
        return profiles

    @staticmethod
    def _check_static_profile_agreement(profiles):
        bound = [p for p in profiles if p is not None]
        if not bound:
            return
        if len(bound) != len(profiles):
            # a partial binding would silently apply pair 0's static
            # policy (or none) to the profile-less pairs — reject
            raise ValueError(
                "portfolio_profiles must cover every pair (or bind one "
                "shared execution_cost_profile): profiles must never be "
                "silently degraded"
            )
        head = bound[0]
        for other in bound[1:]:
            for field in _STATIC_PROFILE_FIELDS:
                if getattr(other, field) != getattr(head, field):
                    raise ValueError(
                        "per-pair profiles must agree on static policy field "
                        f"{field!r} (one XLA program serves all pairs): "
                        f"{getattr(head, field)!r} != {getattr(other, field)!r}"
                    )

    def reset(self):
        return _jit_p_reset(self.cfg, self.params, self.data)

    def step(self, state, actions):
        return _jit_p_step(self.cfg, self.params, self.data, state, actions)


_jit_p_reset = jax.jit(reset, static_argnums=0)
_jit_p_step = jax.jit(step, static_argnums=0)
