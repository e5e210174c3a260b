"""Functional env core: smoke invariants and step/bar timing parity.

Invariant sources: reference tools/smoke_test.py:108-155 (flat => equity
unchanged; buy&hold uptrend => positive return; seeded reproducibility)
and the reference handshake timing (orders fill at next bar open).
"""
import jax
import numpy as np
import pytest

from gymfx_tpu.core import rollout as R
from tests.helpers import make_df, make_env, uptrend_df


def test_flat_driver_leaves_equity_unchanged():
    env = make_env(uptrend_df())
    state, out = env.rollout(R.flat_driver(), steps=30)
    np.testing.assert_allclose(np.asarray(out["equity_delta"]), 0.0, rtol=0, atol=1e-9)
    assert int(state.trade_count) == 0
    assert float(state.commission_paid) == 0.0


def test_buy_hold_on_uptrend_is_profitable():
    env = make_env(uptrend_df())
    state, out = env.rollout(R.buy_hold_driver(), steps=30)
    closes = np.asarray(env.data.close)
    opens = np.asarray(env.data.open)
    # step 0 is the same-bar warmup, so after k steps the env sits on bar
    # k-1; the step-0 order fills at bar 1's open. equity at bar t close
    # = initial + close[t] - open[1]
    expected_delta = closes[29] - opens[1]
    assert float(out["equity_delta"][-1]) == pytest.approx(expected_delta, abs=1e-6)
    assert float(out["equity_delta"][-1]) > 0.0
    assert int(state.trade_count) == 0  # never closed
    assert int(np.asarray(out["position"])[-1]) == 1


def test_step_bar_timing_first_step_does_not_advance():
    env = make_env(uptrend_df())
    state, obs = env.reset()
    assert int(state.t) == 0
    state, obs, r, done, info = env.step(state, 1)
    assert int(info["bar_index"]) == 1      # warmup step stays on bar 1
    assert float(r) == 0.0
    assert int(info["position"]) == 0       # order not yet filled
    state, obs, r, done, info = env.step(state, 0)
    assert int(info["bar_index"]) == 2      # now advanced
    assert int(info["position"]) == 1       # filled at bar 2's open


def test_seeded_rollouts_reproduce_and_differ():
    env = make_env(uptrend_df(60), initial_cash=10000.0)
    _, out1 = env.rollout(R.random_driver(), steps=40, seed=7)
    _, out2 = env.rollout(R.random_driver(), steps=40, seed=7)
    _, out3 = env.rollout(R.random_driver(), steps=40, seed=8)
    np.testing.assert_array_equal(np.asarray(out1["action"]), np.asarray(out2["action"]))
    np.testing.assert_array_equal(np.asarray(out1["equity_delta"]), np.asarray(out2["equity_delta"]))
    assert not np.array_equal(np.asarray(out1["action"]), np.asarray(out3["action"]))


def test_commission_and_slippage_accounting():
    comm, slip = 0.0002, 0.0001
    env = make_env(uptrend_df(), commission=comm, slippage=slip)
    state, out = env.rollout(R.buy_hold_driver(), steps=10)
    opens = np.asarray(env.data.open)
    fill = opens[1] * (1 + slip)
    assert float(state.commission_paid) == pytest.approx(comm * fill, rel=1e-5)
    closes = np.asarray(env.data.close)
    expected_delta = closes[9] - fill - comm * fill
    assert float(out["equity_delta"][-1]) == pytest.approx(expected_delta, abs=1e-6)


def test_long_short_flip_counts_trades_and_double_commission():
    comm = 0.0001
    closes = np.full(20, 1.1)
    env = make_env(make_df(closes), commission=comm)
    # step0: long (warmup); step1: advance, fill long at open[1], action short
    # -> flip fills at open[2]; step2: advance.
    state, obs = env.reset()
    state, *_ = env.step(state, 1)
    state, *_ = env.step(state, 2)
    state, obs_, r, done, info = env.step(state, 0)
    assert int(info["trades"]) == 1          # long closed by the flip
    assert int(info["position"]) == -1
    # commissions: 1 unit on entry + 2 units on flip (close+open legs)
    assert float(info["commission_paid"]) == pytest.approx(comm * 1.1 * 3, rel=1e-5)


def test_hold_actions_do_not_pyramid():
    env = make_env(uptrend_df())
    state, out = env.rollout(
        R.replay_driver(np.array([1, 1, 1, 1, 1])), steps=5
    )
    assert float(np.abs(np.asarray(state.pos))) == 1.0  # position_size, no stacking


def test_min_equity_termination():
    n = 30
    closes = np.concatenate([np.full(5, 1.0), np.full(n - 5, 0.5)])
    env = make_env(make_df(closes), position_size=25000.0, min_equity=100.0,
                   initial_cash=10000.0)
    state, out = env.rollout(R.buy_hold_driver(), steps=20)
    done = np.asarray(out["done"])
    assert done.any()
    k = int(np.argmax(done))
    # equity frozen after termination
    eq = np.asarray(out["equity"])
    np.testing.assert_allclose(eq[k:], eq[k], atol=1e-6)
    assert eq[k] <= 100.0 + 1e-6


def test_termination_reason_distinguishes_bankruptcy_from_exhaustion():
    """Explicit termination_reason (r2 advisor finding, fixed r4): a
    bar-cursor heuristic cannot tell a final-bar bankruptcy from
    exhaustion; the latched state flag can."""
    from gymfx_tpu.core.types import (
        TERMINATION_BANKRUPT,
        TERMINATION_EXHAUSTED,
        TERMINATION_RUNNING,
    )

    # mid-episode bankruptcy
    n = 30
    closes = np.concatenate([np.full(5, 1.0), np.full(n - 5, 0.5)])
    env = make_env(make_df(closes), position_size=25000.0, min_equity=100.0,
                   initial_cash=10000.0)
    state, out = env.rollout(R.buy_hold_driver(), steps=20)
    assert int(state.termination_reason) == TERMINATION_BANKRUPT
    # ordinary exhaustion
    env = make_env(uptrend_df(12))
    state, out = env.rollout(R.flat_driver(), steps=15)
    assert int(state.termination_reason) == TERMINATION_EXHAUSTED
    # a live episode reports running
    env = make_env(uptrend_df(40))
    state, out = env.rollout(R.flat_driver(), steps=5)
    assert int(state.termination_reason) == TERMINATION_RUNNING
    # the advisor's case: equity crashes through the floor ON the final
    # bar — the cursor sits at n_bars-1 (looks exhausted) but the reason
    # says bankrupt
    closes = np.concatenate([np.full(11, 1.0), [0.5]])
    env = make_env(make_df(closes), position_size=25000.0, min_equity=100.0,
                   initial_cash=10000.0)
    state, out = env.rollout(R.buy_hold_driver(), steps=15)
    assert int(state.t) == env.n_bars - 1
    assert int(state.termination_reason) == TERMINATION_BANKRUPT


def test_data_exhaustion_terminates():
    env = make_env(uptrend_df(12))  # 12 bars
    state, out = env.rollout(R.flat_driver(), steps=15)
    done = np.asarray(out["done"])
    # bar index reaches 12 at step 11; step 12 hits exhaustion
    assert not done[10]
    assert done[11] or done[12]
    assert done[-1]


def test_continuous_action_mode_thresholding():
    env = make_env(uptrend_df(), action_space_mode="continuous")
    state, obs = env.reset()
    state, *_ , info = env.step(state, np.array([0.5], np.float32))
    assert int(info["coerced_action"]) == 1
    state, *_, info = env.step(state, np.array([-0.9], np.float32))
    assert int(info["coerced_action"]) == 2
    state, *_, info = env.step(state, np.array([0.1], np.float32))
    assert int(info["coerced_action"]) == 0
    assert int(info["action_diagnostics/continuous_deadband_actions"]) == 1
    assert float(info["action_diagnostics/raw_min"]) == pytest.approx(-0.9)
    assert float(info["action_diagnostics/raw_max"]) == pytest.approx(0.5)


def test_event_overlay_blocks_entries_and_forces_flat():
    n = 20
    closes = np.full(n, 1.1)
    flag = np.zeros(n)
    flag[2:5] = 1.0  # event window over bars 2..4
    df = make_df(closes, extra={"event_no_trade_window_active": flag})
    # The overlay reads the flag at the row the action will be applied on
    # (row t+1 pre-advance — reference app/env.py:397); a step is blocked
    # when it advances INTO a flagged bar (rows 2..4 here).
    env2 = make_env(df, event_context_execution_overlay=True)
    s, _ = env2.reset()
    s, *_ = env2.step(s, 0)       # warmup hold (stays on bar 1)
    s, *_ = env2.step(s, 0)       # advance to row 1 (unflagged)
    s, *_, i2 = env2.step(s, 1)   # advance to row 2 (flagged) -> block entry
    assert int(i2["event_context_action_after_overlay"]) == 0
    assert bool(i2["event_context_blocked_entry"])
    assert int(i2["execution_diagnostics/event_context_blocked_entries"]) == 1
    assert int(i2["position"]) == 0

    # force-flat variant: get long first, then hit the window
    env3 = make_env(df, event_context_execution_overlay=True,
                    event_context_force_flat=True)
    s, _ = env3.reset()
    s, *_ = env3.step(s, 1)       # warmup: long pending
    s, *_, j0 = env3.step(s, 0)   # advance to row 1: long filled at open[1]
    assert int(j0["position"]) == 1
    s, *_, j1 = env3.step(s, 0)   # advance to row 2 (flagged) -> action 3
    assert int(j1["event_context_action_after_overlay"]) == 3
    s, *_, j2 = env3.step(s, 0)   # close order fills at row 3's open
    assert int(j2["position"]) == 0
    assert int(j2["execution_diagnostics/event_context_forced_flat_orders"]) == 1


def test_vmap_batched_envs():
    env = make_env(uptrend_df(60))
    seeds = jax.random.split(jax.random.PRNGKey(0), 8)

    def run(key):
        from gymfx_tpu.core.rollout import rollout, random_driver
        _, out = rollout(env.cfg, env.params, env.data, random_driver(), 30, key)
        return out["equity"]

    eq = jax.vmap(run)(seeds)
    assert eq.shape == (8, 30)
    # different seeds took different paths
    assert len({float(x) for x in eq[:, -1]}) > 1


def test_execution_cost_profile_drives_fill_pricing():
    # profile overrides commission and displaces fills adversely by
    # half-spread + slippage
    profile = {
        "schema_version": "execution_cost_profile.v1",
        "profile_id": "t",
        "commission_rate_per_side": 0.0001,
        "full_spread_rate": 0.0002,
        "slippage_bps_per_side": 1.0,   # 1e-4
        "latency_ms": 0,
        "financing_enabled": False,
        "intrabar_collision_policy": "worst_case",
        "limit_fill_policy": "conservative",
        "margin_model": "standard",
        "enforce_margin_preflight": False,
        "random_seed": 0,
    }
    env = make_env(uptrend_df(), execution_cost_profile=profile)
    adverse = 0.0002 / 2 + 1.0 / 10_000
    assert float(env.params.slippage) == pytest.approx(adverse)
    assert float(env.params.commission) == pytest.approx(0.0001)
    state, out = env.rollout(R.buy_hold_driver(), steps=5)
    opens = np.asarray(env.data.open)
    fill = opens[1] * (1 + adverse)
    assert float(state.commission_paid) == pytest.approx(0.0001 * fill, rel=1e-5)


def test_margin_preflight_denies_undermargined_entries():
    profile = {
        "schema_version": "execution_cost_profile.v1",
        "profile_id": "m", "commission_rate_per_side": 0.0,
        "full_spread_rate": 0.0, "slippage_bps_per_side": 0.0,
        "latency_ms": 0, "financing_enabled": False,
        "intrabar_collision_policy": "worst_case",
        "limit_fill_policy": "conservative", "margin_model": "standard",
        "enforce_margin_preflight": True, "random_seed": 0,
    }
    # 10M units at ~1.1 with 5% margin needs ~550k >> 10k cash -> denied
    env = make_env(uptrend_df(), execution_cost_profile=profile,
                   position_size=10_000_000.0, margin_init=0.05)
    assert env.cfg.enforce_margin_preflight
    s, _ = env.reset()
    s, *_ = env.step(s, 1)
    s, *_, info = env.step(s, 0)
    assert int(info["position"]) == 0  # entry never filled
    assert int(info["execution_diagnostics/preflight_denied"]) == 1

    # an affordable size passes the same gate
    env2 = make_env(uptrend_df(), execution_cost_profile=profile,
                    position_size=1000.0, margin_init=0.05)
    s, _ = env2.reset()
    s, *_ = env2.step(s, 1)
    s, *_, info = env2.step(s, 0)
    assert int(info["position"]) == 1
    assert int(info["execution_diagnostics/preflight_denied"]) == 0


def test_margin_preflight_allows_leveraged_flip():
    # Long 100k units at ~1.1 on 10k cash (leveraged margin): the flip
    # to short must pass preflight — the realized balance is intact even
    # though the cash ledger is deeply negative from the open notional.
    profile = {
        "schema_version": "execution_cost_profile.v1",
        "profile_id": "m2", "commission_rate_per_side": 0.0,
        "full_spread_rate": 0.0, "slippage_bps_per_side": 0.0,
        "latency_ms": 0, "financing_enabled": False,
        "intrabar_collision_policy": "worst_case",
        "limit_fill_policy": "conservative", "margin_model": "leveraged",
        "enforce_margin_preflight": True, "random_seed": 0,
    }
    env = make_env(uptrend_df(), execution_cost_profile=profile,
                   position_size=100_000.0, margin_init=0.05, leverage=20.0)
    s, _ = env.reset()
    s, *_ = env.step(s, 1)          # warmup: long pending
    s, *_, i1 = env.step(s, 2)      # long fills; flip order placed
    assert int(i1["position"]) == 1
    s, *_, i2 = env.step(s, 0)      # flip fills
    assert int(i2["position"]) == -1
    assert int(i2["execution_diagnostics/preflight_denied"]) == 0


def test_bad_margin_model_rejected():
    with pytest.raises(ValueError, match="margin_model"):
        make_env(uptrend_df(), enforce_margin_preflight=True,
                 margin_model="leverged")


# ---------------------------------------------------------------------------
# broker.quantize in pure-f32 mode (the TPU path: jax_enable_x64 off)
# ---------------------------------------------------------------------------
def _f32_quantize(x, tick):
    """Run broker.quantize with x64 disabled (TPU semantics) regardless
    of the suite's x64 default."""
    from gymfx_tpu.core import broker

    with jax.enable_x64(False):
        return np.asarray(
            jax.device_get(broker.quantize(jnp_f32(x), jnp_f32(tick)))
        )


def jnp_f32(x):
    import jax.numpy as jnp

    return jnp.asarray(x, jnp.float32)


def test_quantize_tick_zero_is_identity_in_f32():
    x = np.float32([1.100013, 0.0, -2.5, 1e-7])
    np.testing.assert_array_equal(_f32_quantize(x, 0.0), x)


def test_quantize_on_grid_values_are_fixpoints_in_f32():
    """Quantizing an already-quantized value must be a no-op — the
    apply_fill re-quantization identity snap_in_bar relies on."""
    tick = 1e-5
    xs = np.float32(1.1) + np.float32(tick) * np.arange(-50, 50, dtype=np.float32)
    once = _f32_quantize(xs, tick)
    twice = _f32_quantize(once, tick)
    np.testing.assert_array_equal(once, twice)


def test_quantize_f32_within_one_tick_of_f64_grid():
    """The documented pure-f32 contract (core/broker.py quantize): the
    ratio x/tick keeps ~7 fractional bits at FX magnitudes, so a value
    near a midpoint may flip to the ADJACENT tick vs the f64
    round-half-even — but never further than one tick."""
    rng = np.random.default_rng(11)
    tick = 1e-5
    xs = np.float32(1.1 + rng.uniform(-0.05, 0.05, 512))
    got_idx = np.round(_f32_quantize(xs, tick).astype(np.float64) / tick)
    ref_idx = np.round(xs.astype(np.float64) / tick)
    assert np.max(np.abs(got_idx - ref_idx)) <= 1  # at most adjacent
    # and the bulk of draws (away from midpoints) land on the same tick
    assert (got_idx == ref_idx).mean() > 0.95


def test_quantize_f64_mode_rounds_half_even():
    """With x64 on (the suite default) the ratio x/tick rounds
    HALF-EVEN — the replay venue's rounding mode.  tick=0.25 is exact
    in binary, so the midpoint ratios really are .5 and the tie-break
    is observable (half-away would give 0.25/0.75 here)."""
    from gymfx_tpu.core import broker

    tick = 0.25
    xs = np.float64([0.125, 0.375, 0.625, -0.125])
    got = np.asarray(jax.device_get(broker.quantize(xs, tick)))
    np.testing.assert_allclose(got, [0.0, 0.5, 0.5, -0.0], atol=1e-15)


def test_quantize_composes_under_jit_and_vmap():
    from gymfx_tpu.core import broker

    with jax.enable_x64(False):
        xs = jnp_f32([1.100013, 1.100017, 1.099996])
        direct = jax.device_get(broker.quantize(xs, jnp_f32(1e-5)))
        jitted = jax.device_get(
            jax.jit(lambda v: broker.quantize(v, jnp_f32(1e-5)))(xs)
        )
        vmapped = jax.device_get(
            jax.vmap(lambda v: broker.quantize(v, jnp_f32(1e-5)))(xs)
        )
    np.testing.assert_array_equal(direct, jitted)
    np.testing.assert_array_equal(direct, vmapped)
