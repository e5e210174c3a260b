"""Core state/config/param structures for the functional environment.

The reference keeps episode state in a mutable ``BTBridge`` shared
between two threads (reference app/bt_bridge.py:30-83) plus hidden
state inside plugin objects (reward deques, ATR buffers).  Here ALL of
it is one explicit ``EnvState`` pytree threaded through a pure ``step``
— the precondition for ``jit``/``vmap``/``lax.scan`` and for sharding
state across a device mesh.

Three-way split:
  EnvConfig  static python values (hashable) — changing them recompiles.
  EnvParams  numeric leaves (a pytree) — changing them does NOT recompile;
             this is what optimizers / PBT sweeps mutate.
  EnvState   per-episode carry.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Tuple

import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Diagnostics counter layouts (int32 vectors in EnvState).
# Names mirror the reference diagnostics dicts so info/summary emission is
# key-for-key compatible (reference app/bt_bridge.py:68-83, app/env.py:718-733).
# ---------------------------------------------------------------------------
EXEC_DIAG_KEYS = (
    "entry_actions_seen",
    "entry_orders_submitted",
    "blocked_session_filter",
    "blocked_atr_warmup",
    "blocked_non_positive_atr",
    "blocked_non_positive_size",
    "blocked_non_positive_price",
    "default_orders_submitted",
    "plugin_apply_errors",
    "event_context_no_trade_active_steps",
    "event_context_action_overrides",
    "event_context_blocked_entries",
    "event_context_forced_flat_actions",
    "event_context_forced_flat_orders",
    "preflight_denied",
    "margin_closeouts",
    "order_denied_min_quantity",
)
EXEC_DIAG_INDEX = {k: i for i, k in enumerate(EXEC_DIAG_KEYS)}

# EnvState.termination_reason codes (why `terminated` first became True;
# 0 while running).  An explicit flag — the bar cursor cannot distinguish
# a bankruptcy ON the final bar from ordinary exhaustion (r2 advisor
# finding, fixed r4).
TERMINATION_RUNNING = 0
TERMINATION_BANKRUPT = 1
TERMINATION_EXHAUSTED = 2
TERMINATION_REASONS = ("running", "bankrupt", "exhausted")

ACTION_DIAG_KEYS = (
    "steps",
    "hold_actions",
    "long_actions",
    "short_actions",
    "non_hold_actions",
    "continuous_deadband_actions",
)
ACTION_DIAG_INDEX = {k: i for i, k in enumerate(ACTION_DIAG_KEYS)}


@dataclasses.dataclass(frozen=True)
class EnvConfig:
    """Static environment configuration (trace-time constants)."""

    window_size: int = 32
    n_bars: int = 0
    n_features: int = 0
    binary_mask: Tuple[bool, ...] = ()
    feature_clip: float = 10.0

    action_space_mode: str = "discrete"      # discrete | continuous
    # widen the discrete space to include 3=force-flat as a PUBLIC
    # action (the portfolio env's per-pair action set; in the
    # single-pair env 3 stays internal to the event overlay)
    allow_flat_action: bool = False
    include_prices: bool = True
    include_agent_state: bool = True
    stage_b_force_close_obs: bool = False
    oanda_fx_calendar_obs: bool = False

    event_context_execution_overlay: bool = False
    event_context_block_new_entries: bool = True
    event_context_force_flat: bool = False

    strategy: str = "default"                # default | direct_fixed_sltp | direct_atr_sltp | registered kernel
    session_filter: bool = False
    sltp_risk_mode: str = "fixed_atr"        # fixed_atr | rel_volume_aware_atr | margin_aware_atr
    size_mode: str = "fx_units"              # fx_units | notional
    atr_period: int = 14

    reward: str = "pnl_reward"               # pnl_reward | sharpe_reward | dd_penalized_reward | registered kernel
    obs_kernels: Tuple[str, ...] = ()        # registered extra obs blocks
    # per-step fused feature scaling (ops/window_zscore.fused_step_obs):
    # off|on|interpret resolve in ops/dispatch.py: "on" = the compiled
    # kernel (or its error) on a TPU, plain XLA on a CPU; "interpret" =
    # pallas interpret mode on any backend (CPU parity tests); "off" =
    # plain XLA everywhere (the bitwise oracle)
    rollout_obs_kernel: str = "off"          # off | on | interpret
    # fused env-dynamics kernels (ops/env_dynamics.py): the bar venue's
    # fill/bracket/financing pass and the mark/reward pass each become
    # one env-blocked pallas VMEM pass bracketing the strategy kernel.
    # Same mode contract as rollout_obs_kernel; "off" is the plain-XLA
    # bitwise oracle (tests/test_env_dynamics_kernel.py pins parity).
    rollout_env_kernel: str = "off"          # off | on | interpret
    sharpe_window: int = 64
    stage_b_force_close_reward_penalty: bool = False

    # execution venue: "bar" = the broker scan (fill at next open,
    # brackets vs H/L); "lob" = the vectorized limit-order-book engine
    # (gymfx_tpu/lob/): agent orders walk a seeded book driven by a
    # deterministic per-bar message flow.  Static so the bar path stays
    # bitwise identical when unset — the LOB branch is never traced.
    venue: str = "bar"                       # bar | lob
    lob_depth_levels: int = 24               # price levels per side
    lob_queue_slots: int = 4                 # FIFO orders per level
    lob_messages_per_bar: int = 64           # flow messages per bar (static)
    lob_seed_levels: int = 8                 # seeded levels per side at open
    lob_flow_seed: int = 0                   # order-flow PRNG seed
    lob_scenario: str = "lob_calm"           # lob/scenarios.py preset
    lob_tick_size: float = 1e-5              # quote-currency size of one tick
    lob_lot_units: float = 0.0               # units per lot (0 = position_size)
    # pallas LOB matching (ops/lob_match.py): the sort-free ranked
    # matcher replaces the per-message argsort walk for stream
    # processing (book seeding + the bench depth sweep), exact int32
    # parity with lob/book.py (tests/test_lob_match_kernel.py)
    lob_match_kernel: str = "off"            # off | on | interpret
    # feed=scengen + venue=lob: derive per-bar FlowParams from the
    # generated tape's scen_flags (lob/scenarios.flow_params_from_regime)
    # so droughts thin the book and crash bars burst the flow.  Static:
    # when off (every replay feed) the scen_flags leaf is never traced.
    lob_flow_from_scengen: bool = False

    intrabar_collision_policy: str = "worst_case"  # worst_case | adaptive | ohlc
    # "cross" (price-improving gap fills) is the scan engine's historical
    # no-profile behavior; profiles always set the field explicitly.
    limit_fill_policy: str = "cross"               # conservative | touch | cross
    enforce_margin_preflight: bool = False
    # maintenance-margin liquidation: equity below the maintenance
    # requirement at a bar close force-flattens at the next bar open
    # (reference: Nautilus margin account via margin_maint,
    # simulation_engines/contracts.py:117-120, nautilus_adapter.py:397-427)
    enforce_margin_closeout: bool = False
    margin_model: str = "leveraged"                # standard | leveraged
    financing_enabled: bool = False                # FX rollover interest accrual

    # per-fill-type slippage switches — the reference broker's
    # set_slippage_perc(slip_open, slip_limit, slip_match)
    # (broker_plugins/default_broker.py:52, backtrader semantics).
    # Scan defaults keep the engine's historical behavior (market/stop
    # fills slip, limit fills exempt, no bar-range cap); the reference's
    # backtrader run enables all three — set them in the config to match.
    slip_open: bool = True    # slippage on fills executing at the bar open
    slip_limit: bool = False  # slippage on limit (TP) fills, capped at the limit price
    slip_match: bool = False  # cap slipped fill prices into the bar's [low, high]

    dtype: Any = jnp.float32

    def __post_init__(self):
        from gymfx_tpu.ops.dispatch import KERNEL_MODES
        from gymfx_tpu.plugins import kernels as _k

        if self.action_space_mode not in ("discrete", "continuous"):
            raise ValueError("action_space_mode must be discrete|continuous")
        if self.strategy not in _k.BUILTIN_STRATEGIES and not _k.has_strategy_kernel(
            self.strategy
        ):
            raise ValueError(f"unknown strategy kernel {self.strategy!r}")
        if self.reward not in _k.BUILTIN_REWARDS and not _k.has_reward_kernel(
            self.reward
        ):
            raise ValueError(f"unknown reward kernel {self.reward!r}")
        for name in self.obs_kernels:
            if not _k.has_obs_kernel(name):
                raise ValueError(f"unknown obs kernel {name!r}")
        if self.rollout_obs_kernel not in KERNEL_MODES:
            raise ValueError(
                f"rollout_obs_kernel must be off|on|interpret, got "
                f"{self.rollout_obs_kernel!r}"
            )
        if self.rollout_obs_kernel != "off" and self.n_features == 0:
            # honor-or-reject: core/obs.build_obs only scales feature
            # windows when the dataset has feature columns; with none
            # the kernel would never enter the program
            raise ValueError(
                "rollout_obs_kernel requires feature columns "
                "(n_features > 0): with none configured there is no "
                "feature window for the kernel to scale"
            )
        if self.rollout_env_kernel not in KERNEL_MODES:
            raise ValueError(
                f"rollout_env_kernel must be off|on|interpret, got "
                f"{self.rollout_env_kernel!r}"
            )
        if self.rollout_env_kernel != "off":
            # honor-or-reject: the fused dynamics kernels cover exactly
            # the bar venue's fill/bracket/mark/reward scalar ledger.
            # Anything they cannot reproduce bitwise fails loudly here
            # instead of silently degrading (validate_lob_venue pattern).
            if self.venue != "bar":
                raise ValueError(
                    "rollout_env_kernel requires venue='bar' (the LOB "
                    "venue's matching has its own kernel knob, "
                    "lob_match_kernel)"
                )
            if self.reward not in ("pnl_reward", "dd_penalized_reward"):
                raise ValueError(
                    "rollout_env_kernel supports reward kernels with "
                    "packed scalar carries (pnl_reward, "
                    "dd_penalized_reward); sharpe_reward's per-env ring "
                    f"buffer and registered kernels are XLA-only, got "
                    f"{self.reward!r}"
                )
            if self.dtype != jnp.float32:
                raise ValueError(
                    "rollout_env_kernel requires compute_dtype float32 "
                    f"(got {self.dtype!r}); the f64 oracle mode stays on "
                    "the plain-XLA path"
                )
        if self.lob_match_kernel not in KERNEL_MODES:
            raise ValueError(
                f"lob_match_kernel must be off|on|interpret, got "
                f"{self.lob_match_kernel!r}"
            )
        if self.margin_model not in ("standard", "leveraged"):
            raise ValueError(f"unknown margin_model {self.margin_model!r}")
        if self.intrabar_collision_policy not in ("worst_case", "adaptive", "ohlc"):
            raise ValueError(
                f"unknown intrabar_collision_policy {self.intrabar_collision_policy!r}"
            )
        if self.limit_fill_policy not in ("conservative", "touch", "cross"):
            raise ValueError(
                f"unknown limit_fill_policy {self.limit_fill_policy!r}"
            )
        if self.venue not in ("bar", "lob"):
            raise ValueError(f"venue must be bar|lob, got {self.venue!r}")
        if self.venue == "lob":
            if self.lob_depth_levels < 2:
                raise ValueError("lob_depth_levels must be >= 2")
            if self.lob_queue_slots < 1:
                raise ValueError("lob_queue_slots must be >= 1")
            if self.lob_messages_per_bar < 1:
                raise ValueError("lob_messages_per_bar must be >= 1")
            if not 0 <= self.lob_seed_levels <= self.lob_depth_levels:
                raise ValueError(
                    "lob_seed_levels must be in [0, lob_depth_levels]"
                )
            if self.lob_tick_size <= 0:
                raise ValueError("lob_tick_size must be > 0")
            if self.lob_lot_units < 0:
                raise ValueError("lob_lot_units must be >= 0")
            from gymfx_tpu.lob.scenarios import scenario_flow_params

            scenario_flow_params(self.lob_scenario)  # honor-or-reject


class EnvParams(NamedTuple):
    """Numeric environment parameters (pytree leaves; no recompilation)."""

    initial_cash: Any
    position_size: Any
    commission: Any            # fraction of notional per executed order
    slippage: Any              # fraction of price per fill
    leverage: Any
    min_equity: Any
    continuous_action_threshold: Any

    # reward family
    reward_scale: Any
    penalty_lambda: Any
    annualization_factor: Any

    # fixed-sltp strategy
    sl_pips: Any
    tp_pips: Any
    pip_size: Any

    # atr-sltp strategy
    k_sl: Any
    k_tp: Any
    use_rel_volume: Any        # 0/1 flag (reference: rel_volume=None disables)
    rel_volume: Any
    min_order_volume: Any
    max_order_volume: Any
    min_sltp_frac: Any         # <0 disables
    max_sltp_frac: Any         # <0 disables
    baseline_rel_volume: Any
    max_risk_rel_volume: Any
    rel_volume_sl_shrink_alpha: Any
    rel_volume_tp_shrink_alpha: Any
    min_k_sl: Any
    min_reward_risk_ratio: Any
    max_planned_loss_fraction: Any  # <0 disables

    # session/weekend filter (minute-of-week bounds)
    entry_start_mow: Any
    force_close_mow: Any

    # event-context overlay
    event_no_trade_threshold: Any

    # stage-B force-close reward penalty
    force_close_penalty_coef: Any
    force_close_penalty_window_hours: Any

    # margin (instrument initial / maintenance fractions)
    margin_init: Any
    margin_maint: Any

    # opt-in venue quantization (0 = off): book-price tick, order-size
    # step, minimum order quantity — the scan twins of the replay
    # venue's make_price/make_qty/min_quantity (simulation/replay.py;
    # reference nautilus_adapter.py:111-113,190).  Params-only sentinel
    # design: enabling it never recompiles the step.
    price_tick: Any = 0.0
    size_step: Any = 0.0
    min_qty: Any = 0.0

    # registered third-party kernel parameters ({config_key: scalar});
    # an empty tuple when no custom kernel is selected
    user: Any = ()


class EnvState(NamedTuple):
    """Per-episode carry threaded through the scan."""

    t: Any                 # i32 current bar row (0-based); bar_index = t + 1
    started: Any           # bool — warmup handshake done (reference bt_bridge.py:144-151)
    terminated: Any        # bool
    termination_reason: Any  # i32 TERMINATION_* code (0 while running)

    # broker ledger (all in quote currency, relative to initial cash)
    pos: Any               # signed units
    entry_price: Any       # avg entry price of open position
    cash_delta: Any        # cash - initial_cash
    equity_delta: Any      # marked at close of bar t
    prev_equity_delta: Any
    commission_paid: Any
    last_trade_cost: Any
    trade_count: Any       # i32 closed trades

    # pending order (created at bar t close, fills at bar t+1 open)
    pending_active: Any    # bool
    pending_target: Any    # desired signed units
    pending_sl: Any        # bracket prices to arm after fill (0 = none)
    pending_tp: Any
    # venue-forced liquidation flag: the pending order was created by the
    # maintenance-margin closeout, not the agent — it bypasses the venue's
    # min-quantity/size-step rules exactly like the replay engine's
    # liquidation ("a venue never strands a liquidation on a size rule",
    # simulation/replay.py check_margin_closeout)
    pending_forced: Any    # bool

    # active bracket on the open position (0 = none)
    bracket_sl: Any
    bracket_tp: Any

    # trade statistics (for SQN / won / lost / avg pnl)
    trade_pnl_sum: Any
    trade_pnl_sumsq: Any
    trades_won: Any        # i32
    trades_lost: Any       # i32
    open_trade_commission: Any  # commissions attributed to the open trade

    # drawdown tracking
    peak_equity_delta: Any
    max_drawdown_money: Any
    max_drawdown_pct: Any

    # reward carries
    reward_buffer: Any     # (sharpe_window,) step returns ring buffer
    reward_buffer_len: Any # i32
    reward_buffer_idx: Any # i32
    reward_peak: Any       # dd_penalized peak equity

    # ATR true-range ring buffer (direct_atr_sltp)
    tr_buffer: Any         # (atr_period,)
    tr_len: Any            # i32
    tr_idx: Any            # i32
    prev_close: Any        # previous bar close (<=0 sentinel: none yet)

    # streaming observation windows.  Kept as carries and updated
    # incrementally (shift + append) on each bar advance: a vmapped
    # dynamic_slice gather per step costs ~15x the entire env step on
    # TPU, while the streaming update is pure vector ops.
    price_window: Any      # (window_size,) close window ending at the current bar
    feat_window: Any       # (window_size, n_features) raw feature window

    # diagnostics
    exec_diag: Any         # (len(EXEC_DIAG_KEYS),) i32
    action_diag: Any       # (len(ACTION_DIAG_KEYS),) i32
    raw_abs_sum: Any
    raw_min: Any
    raw_max: Any
    last_raw_action: Any
    last_coerced_action: Any  # i32


# ---------------------------------------------------------------------------
# Builders from a merged config dict
# ---------------------------------------------------------------------------
def _parse_profile(config: Dict[str, Any]):
    raw = config.get("execution_cost_profile")
    if not raw:
        return None
    from gymfx_tpu.contracts import ExecutionCostProfile, load_execution_cost_profile

    if isinstance(raw, str):
        return load_execution_cost_profile(raw)
    if isinstance(raw, dict):
        return ExecutionCostProfile.from_dict(raw)
    return raw


def make_env_config(config: Dict[str, Any], *, n_bars: int, n_features: int = 0,
                    binary_mask: Tuple[bool, ...] = (), profile=None) -> EnvConfig:
    feature_columns = list(config.get("feature_columns") or [])
    include_prices = bool(config.get("include_price_window", not feature_columns))
    oanda_cal = bool(
        config.get("oanda_fx_calendar_obs", False)
        or str(config.get("broker_profile") or "").lower() == "oanda_us_fx"
    )
    dtype = {"float32": jnp.float32, "float64": jnp.float64, "bfloat16": jnp.bfloat16}[
        str(config.get("compute_dtype", "float32"))
    ]
    profile = _parse_profile(config) if profile is None else profile
    collision = str(
        config.get(
            "intrabar_collision_policy",
            profile.intrabar_collision_policy if profile else "worst_case",
        )
    )
    enforce_margin = bool(
        config.get(
            "enforce_margin_preflight",
            profile.enforce_margin_preflight if profile else False,
        )
    )
    # maintenance enforcement follows the preflight flag by default (one
    # venue either runs a margin account or does not — the reference's
    # Nautilus engine enforces both implicitly); the explicit config key
    # overrides either way
    enforce_closeout = bool(config.get("enforce_margin_closeout", enforce_margin))
    margin_model = str(
        config.get("margin_model", profile.margin_model if profile else "leveraged")
    )
    limit_fill = str(
        config.get(
            "limit_fill_policy",
            profile.limit_fill_policy if profile else "cross",
        )
    )
    financing = bool(
        config.get(
            "financing_enabled",
            profile.financing_enabled if profile else False,
        )
    )
    if collision == "adaptive":
        import warnings

        warnings.warn(
            "intrabar_collision_policy 'adaptive' resolves to 'worst_case' in "
            "the scan engine (no per-bar path data to adapt on); see "
            "DIVERGENCES.md",
            stacklevel=2,
        )
    return EnvConfig(
        window_size=int(config.get("window_size", 32)),
        n_bars=int(n_bars),
        n_features=int(n_features),
        binary_mask=tuple(binary_mask),
        feature_clip=float(config.get("feature_clip", 10.0)),
        action_space_mode=str(config.get("action_space_mode", "discrete")).lower(),
        include_prices=include_prices,
        include_agent_state=bool(config.get("include_agent_state", True)),
        stage_b_force_close_obs=bool(config.get("stage_b_force_close_obs", False)),
        oanda_fx_calendar_obs=oanda_cal,
        event_context_execution_overlay=bool(
            config.get("event_context_execution_overlay", False)
        ),
        event_context_block_new_entries=bool(
            config.get("event_context_block_new_entries", True)
        ),
        event_context_force_flat=bool(config.get("event_context_force_flat", False)),
        strategy=_strategy_kernel_name(config),
        session_filter=bool(config.get("session_filter", False)),
        sltp_risk_mode=str(config.get("sltp_risk_mode", "fixed_atr")).lower(),
        size_mode=str(config.get("size_mode", "fx_units")).lower(),
        atr_period=int(config.get("atr_period", 14)),
        reward=str(config.get("reward_plugin", "pnl_reward")),
        obs_kernels=_obs_kernel_names(config.get("obs_plugins")),
        rollout_obs_kernel=str(config.get("rollout_obs_kernel", "off")).lower(),
        rollout_env_kernel=str(config.get("rollout_env_kernel", "off")).lower(),
        sharpe_window=int(config.get("window", config.get("sharpe_window", 64))),
        stage_b_force_close_reward_penalty=bool(
            config.get("stage_b_force_close_reward_penalty", False)
        ),
        venue=str(config.get("venue", "bar")).lower(),
        lob_depth_levels=int(config.get("lob_depth_levels", 24)),
        lob_queue_slots=int(config.get("lob_queue_slots", 4)),
        lob_messages_per_bar=int(config.get("lob_messages_per_bar", 64)),
        lob_seed_levels=int(config.get("lob_seed_levels", 8)),
        lob_flow_seed=int(config.get("lob_flow_seed", 0)),
        lob_scenario=str(config.get("lob_scenario", "lob_calm")),
        lob_tick_size=float(config.get("lob_tick_size", 1e-5)),
        lob_lot_units=float(config.get("lob_lot_units", 0.0)),
        lob_match_kernel=str(config.get("lob_match_kernel", "off")).lower(),
        lob_flow_from_scengen=(
            str(config.get("feed") or "replay").lower() == "scengen"
            and str(config.get("venue", "bar")).lower() == "lob"
        ),
        intrabar_collision_policy=collision,
        limit_fill_policy=limit_fill,
        slip_open=bool(config.get("slip_open", True)),
        slip_limit=bool(config.get("slip_limit", False)),
        slip_match=bool(config.get("slip_match", False)),
        enforce_margin_preflight=enforce_margin,
        enforce_margin_closeout=enforce_closeout,
        margin_model=margin_model,
        financing_enabled=financing,
        dtype=dtype,
    )


def _obs_kernel_names(raw: Any) -> Tuple[str, ...]:
    """obs_plugins accepts a list OR the CLI's comma-separated string —
    tuple() on a bare string would split it into characters."""
    if not raw:
        return ()
    if isinstance(raw, str):
        return tuple(s.strip() for s in raw.split(",") if s.strip())
    return tuple(str(s) for s in raw)


def _strategy_kernel_name(config: Dict[str, Any]) -> str:
    name = str(config.get("strategy_plugin", "default_strategy"))
    if name in ("direct_fixed_sltp", "direct_atr_sltp"):
        return name
    if name in ("default", "default_strategy"):
        # the reference's default_strategy is an action DRIVER, not an
        # executor; the kernel equivalent is the default order flow
        return "default"
    from gymfx_tpu.plugins import kernels as _k

    if _k.has_strategy_kernel(name):
        return name
    raise ValueError(
        f"unknown strategy kernel {name!r}: not a built-in and not a "
        "registered strategy kernel (plugins/kernels.py)"
    )


def make_env_params(config: Dict[str, Any], cfg: EnvConfig, profile=None) -> EnvParams:
    d = cfg.dtype
    initial_cash = float(config.get("initial_cash", 10000.0))
    min_equity = config.get("min_equity")
    if min_equity is None:
        min_equity = initial_cash * 0.01  # reference app/env.py:122
    rel_volume = config.get("rel_volume")
    use_rel = rel_volume is not None

    def f(x) -> Any:
        return jnp.asarray(float(x), dtype=d)

    def opt(x, disabled=-1.0) -> Any:
        return f(disabled if x is None else x)

    slippage = config.get("slippage_perc", config.get("slippage", 0.0)) or 0.0
    commission = config.get("commission", 0.0)
    # An execution cost profile (path or dict) overrides commission and
    # fill displacement: fills move adversely from mid by
    # half-spread + slippage (contracts.py quote_adverse_rate_per_side).
    # The reference applies profiles only on its Nautilus engine
    # (simulation_engines/nautilus_gym.py:236-238); the scan engine
    # honors them directly.
    profile = _parse_profile(config) if profile is None else profile
    if profile is not None:
        commission = profile.commission_rate_per_side
        slippage = profile.quote_adverse_rate_per_side
    entry_start_mow = (
        int(config.get("entry_dow_start", 0)) * 24 * 60
        + int(config.get("entry_hour_start", 12)) * 60
    )
    force_close_mow = (
        int(config.get("force_close_dow", 4)) * 24 * 60
        + int(config.get("force_close_hour", 20)) * 60
    )
    return EnvParams(
        initial_cash=f(initial_cash),
        position_size=f(config.get("position_size", 1.0)),
        commission=f(commission),
        slippage=f(slippage),
        leverage=f(config.get("leverage", 1.0)),
        min_equity=f(min_equity),
        continuous_action_threshold=f(
            0.33
            if config.get("continuous_action_threshold", 0.33) is None
            else config.get("continuous_action_threshold", 0.33)
        ),
        reward_scale=f(config.get("reward_scale", 1.0)),
        penalty_lambda=f(config.get("penalty_lambda", 1.0)),
        annualization_factor=f(config.get("annualization_factor", 252.0)),
        sl_pips=f(config.get("sl_pips", 20.0)),
        tp_pips=f(config.get("tp_pips", 40.0)),
        pip_size=f(config.get("pip_size", 0.0001)),
        k_sl=f(config.get("k_sl", 2.0)),
        k_tp=f(config.get("k_tp", 3.0)),
        use_rel_volume=f(1.0 if use_rel else 0.0),
        rel_volume=f(rel_volume if use_rel else 0.0),
        min_order_volume=f(config.get("min_order_volume", 0.0)),
        max_order_volume=f(config.get("max_order_volume", 1e12)),
        min_sltp_frac=opt(config.get("min_sltp_frac", 0.001)),
        max_sltp_frac=opt(config.get("max_sltp_frac", 0.20)),
        baseline_rel_volume=f(config.get("baseline_rel_volume", 0.05)),
        max_risk_rel_volume=f(config.get("max_risk_rel_volume", 0.50)),
        rel_volume_sl_shrink_alpha=f(config.get("rel_volume_sl_shrink_alpha", 0.35)),
        rel_volume_tp_shrink_alpha=f(config.get("rel_volume_tp_shrink_alpha", 0.20)),
        min_k_sl=f(config.get("min_k_sl", 1.0)),
        min_reward_risk_ratio=f(config.get("min_reward_risk_ratio", 1.0)),
        max_planned_loss_fraction=opt(config.get("max_planned_loss_fraction")),
        entry_start_mow=jnp.asarray(entry_start_mow, dtype=jnp.int32),
        force_close_mow=jnp.asarray(force_close_mow, dtype=jnp.int32),
        event_no_trade_threshold=f(config.get("event_context_no_trade_threshold", 0.5)),
        force_close_penalty_coef=f(
            config.get("force_close_exposure_penalty_coef", 0.0)
        ),
        margin_init=f(config.get("margin_init", 0.05)),
        margin_maint=f(config.get("margin_maint", 0.025)),
        **_venue_quantization_params(config, f),
        force_close_penalty_window_hours=f(
            config.get(
                "force_close_exposure_penalty_window_hours",
                config.get("force_close_window_hours", 4),
            )
        ),
        user=_user_params(config, cfg, f),
    )


def _venue_quantization_params(config: Dict[str, Any], f) -> Dict[str, Any]:
    """Opt-in (``venue_quantization: true``): derive tick/step/min-qty
    from the instrument spec resolved exactly as the replay engine does
    (contracts.instrument_spec_from_config), so both engines quantize to
    the same grid.  Off -> zero sentinels, the step is untouched."""
    if not config.get("venue_quantization"):
        return {"price_tick": f(0.0), "size_step": f(0.0), "min_qty": f(0.0)}
    from gymfx_tpu.contracts import instrument_spec_from_config

    spec = instrument_spec_from_config(config)
    return {
        "price_tick": f(10.0 ** (-spec.price_precision)),
        "size_step": f(10.0 ** (-spec.size_precision)),
        "min_qty": f(spec.min_quantity),
    }


def _user_params(config: Dict[str, Any], cfg: EnvConfig, f) -> Any:
    """Numeric parameters declared by the selected registered kernels,
    read from the merged config (plugins/kernels.py contract)."""
    from gymfx_tpu.plugins import kernels as _k

    schema = _k.user_param_schema(cfg.reward, cfg.strategy, cfg.obs_kernels)
    if not schema:
        return ()
    return {
        key: f(config.get(key, default) if config.get(key) is not None else default)
        for key, default in sorted(schema.items())
    }


def initial_state(cfg: EnvConfig) -> EnvState:
    d = cfg.dtype
    z = jnp.zeros((), dtype=d)
    zi = jnp.zeros((), dtype=jnp.int32)

    return EnvState(
        t=zi,
        started=jnp.zeros((), dtype=bool),
        terminated=jnp.zeros((), dtype=bool),
        termination_reason=zi,
        pos=z,
        entry_price=z,
        cash_delta=z,
        equity_delta=z,
        prev_equity_delta=z,
        commission_paid=z,
        last_trade_cost=z,
        trade_count=zi,
        pending_active=jnp.zeros((), dtype=bool),
        pending_target=z,
        pending_sl=z,
        pending_tp=z,
        pending_forced=jnp.zeros((), dtype=bool),
        bracket_sl=z,
        bracket_tp=z,
        trade_pnl_sum=z,
        trade_pnl_sumsq=z,
        trades_won=zi,
        trades_lost=zi,
        open_trade_commission=z,
        peak_equity_delta=z,
        max_drawdown_money=z,
        max_drawdown_pct=z,
        reward_buffer=jnp.zeros((cfg.sharpe_window,), dtype=d),
        reward_buffer_len=zi,
        reward_buffer_idx=zi,
        reward_peak=jnp.asarray(-np.inf, dtype=d),  # delta-space peak
        tr_buffer=jnp.zeros((cfg.atr_period,), dtype=d),
        tr_len=zi,
        tr_idx=zi,
        prev_close=jnp.asarray(-1.0, dtype=d),
        price_window=jnp.zeros((cfg.window_size,), dtype=d),
        feat_window=jnp.zeros((cfg.window_size, cfg.n_features), dtype=jnp.float32),
        exec_diag=jnp.zeros((len(EXEC_DIAG_KEYS),), dtype=jnp.int32),
        action_diag=jnp.zeros((len(ACTION_DIAG_KEYS),), dtype=jnp.int32),
        raw_abs_sum=z,
        raw_min=jnp.asarray(np.inf, dtype=d),
        raw_max=jnp.asarray(-np.inf, dtype=d),
        last_raw_action=z,
        last_coerced_action=zi,
    )
