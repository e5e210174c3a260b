"""Generative scenario suite (gymfx_tpu/scengen/, docs/scenarios.md).

The contract under test, layer by layer:

  * engine vs oracle — the lax.scan transform and the independently
    written NumPy loop consume the SAME drawn shocks; regimes and flags
    must match EXACTLY (decision-critical comparisons are sequenced f32
    in both), prices to float tolerance;
  * statistical pins — each preset's tape exhibits its signature
    hazards at the parameterized rates, tolerance-bounded;
  * determinism — same seed + preset => bitwise-identical frames, in
    process and across two subprocesses;
  * wiring — feed=replay stays bitwise identical with the feed key
    unset; feed=scengen trains PPO end-to-end on multiple presets,
    splits chronologically, and drives the LOB flow from the tape's
    regime flags; the fault-profile ``scengen=`` clause stresses a
    replayed tape; the scenario gate emits a schema-valid report.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from gymfx_tpu.config import DEFAULT_VALUES
from gymfx_tpu.core.rollout import buy_hold_driver, rollout
from gymfx_tpu.core.runtime import Environment
from gymfx_tpu.scengen.engine import draw_shocks, generate, paths_from_shocks
from gymfx_tpu.scengen.feed import (
    ScenGenDataset,
    fx_timestamp_grid,
    synthesize_frame,
)
from gymfx_tpu.scengen.oracle import oracle_paths
from gymfx_tpu.scengen.params import (
    FLAG_CRASH,
    FLAG_DROUGHT,
    FLAG_GAP,
    preset_names,
    scenario_params,
)

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "tools"))

from scenario_gate import run_gate, validate_report  # noqa: E402


def _parity_pair(preset: str, n_bars: int, n_assets: int, seed: int = 0):
    p = scenario_params(preset)
    shocks = draw_shocks(jax.random.PRNGKey(seed), n_bars, n_assets)
    monday = np.zeros(n_bars, bool)
    got = jax.tree.map(np.asarray, paths_from_shocks(shocks, p, monday))
    want = oracle_paths(jax.tree.map(np.asarray, shocks), p, monday)
    return got, want


# ----------------------------------------------------------------------
# engine vs NumPy oracle


@pytest.mark.parametrize(
    "preset,n_assets",
    [("regime_mix", 1), ("flash_crash", 1), ("liquidity_drought", 1),
     ("gap_open", 1), ("trend_calm", 1), ("multi_asset_stress", 3)],
)
def test_oracle_parity_decisions_exact_prices_close(preset, n_assets):
    got, want = _parity_pair(preset, 512, n_assets)
    # decision channels: EXACT (sequenced f32 comparisons on both sides)
    np.testing.assert_array_equal(got.regime, want["regime"], err_msg=preset)
    np.testing.assert_array_equal(got.flags, want["flags"], err_msg=preset)
    np.testing.assert_allclose(
        got.spread_mult, want["spread_mult"], rtol=1e-6, err_msg=preset
    )
    np.testing.assert_allclose(
        got.slip_mult, want["slip_mult"], rtol=1e-6, err_msg=preset
    )
    # prices: float tolerance (exp/matmul associativity differs)
    for field in ("open", "high", "low", "close"):
        np.testing.assert_allclose(
            getattr(got, field), want[field], rtol=5e-4,
            err_msg=f"{preset}:{field}",
        )
    assert np.all(got.low <= got.high)
    assert np.all(got.low > 0)


def test_oracle_parity_honors_weekend_mask():
    p = scenario_params("gap_open")
    n = 256
    shocks = draw_shocks(jax.random.PRNGKey(3), n, 1)
    monday = np.zeros(n, bool)
    monday[[40, 110, 180]] = True
    got = jax.tree.map(np.asarray, paths_from_shocks(shocks, p, monday))
    want = oracle_paths(jax.tree.map(np.asarray, shocks), p, monday)
    np.testing.assert_array_equal(got.flags, want["flags"])
    # every Monday-open bar is a gap bar by construction
    assert np.all(got.flags[monday] & FLAG_GAP != 0)


# ----------------------------------------------------------------------
# per-preset statistical pins (satellite: tolerance-bounded moments)


def test_statistical_pins_trend_and_chop_moments():
    n = 4096
    _, trend = _parity_pair("trend_calm", n, 1, seed=1)
    ret = np.diff(np.log(trend["close"][:, 0].astype(np.float64)))
    # drift pins: trend_calm lives in TREND_UP (drift 5e-5, vol 2e-4)
    assert 2e-5 < float(ret.mean()) < 9e-5, ret.mean()
    assert 1.2e-4 < float(ret.std()) < 3.0e-4, ret.std()

    _, chop = _parity_pair("range_chop", n, 1, seed=1)
    ret_c = np.diff(np.log(chop["close"][:, 0].astype(np.float64)))
    assert abs(float(ret_c.mean())) < 2e-5, ret_c.mean()
    assert 1.0e-4 < float(ret_c.std()) < 2.4e-4, ret_c.std()


def test_statistical_pins_flash_crash_drawdown_band():
    n = 4096
    got, want = _parity_pair("flash_crash", n, 1, seed=2)
    close = want["close"][:, 0].astype(np.float64)
    peak = np.maximum.accumulate(close)
    max_dd = float(np.max(1.0 - close / peak))
    # one crash is a 2% drop recovering 60%: the tape must show at least
    # one real drawdown but never a collapse
    assert 0.012 < max_dd < 0.5, max_dd
    crash_frac = float(np.mean(want["flags"] & FLAG_CRASH != 0))
    # expected rate ~ p_crash * crash_len = 0.004 * 6 = 2.4% of bars
    assert 0.004 < crash_frac < 0.08, crash_frac
    # crash bars blow the spread out by the parameterized multiplier
    p = scenario_params("flash_crash")
    in_crash = want["flags"] & FLAG_CRASH != 0
    assert float(want["spread_mult"][in_crash].min()) >= float(p.crash_spread)


def test_statistical_pins_gap_frequency_and_drought_blowout():
    n = 4096
    _, gap = _parity_pair("gap_open", n, 1, seed=3)
    gap_frac = float(np.mean(gap["flags"] & FLAG_GAP != 0))
    # no calendar in the direct path: all gaps are random at p_gap=0.02
    assert 0.010 < gap_frac < 0.035, gap_frac

    _, dr = _parity_pair("liquidity_drought", n, 1, seed=3)
    in_drought = dr["flags"] & FLAG_DROUGHT != 0
    frac = float(np.mean(in_drought))
    # expected rate ~ p_drought * drought_len = 0.004 * 32 = 12.8% of bars
    assert 0.03 < frac < 0.35, frac
    p = scenario_params("liquidity_drought")
    # spread blowout magnitude: drought bars carry the full multiplier
    assert float(dr["spread_mult"][in_drought].min()) >= float(
        p.drought_spread
    )
    assert float(dr["spread_mult"][~in_drought].max()) < float(
        p.drought_spread
    )
    # droughts also THIN the tape: quieter returns inside the window
    ret = np.diff(np.log(dr["close"][:, 0].astype(np.float64)))
    assert float(ret[in_drought[1:]].std()) < float(ret[~in_drought[1:]].std())


def test_multi_asset_correlation_pin():
    p = scenario_params("multi_asset_calm")
    paths = generate(p, jax.random.PRNGKey(0), 2048, n_assets=4)
    close = np.asarray(paths.close, np.float64)
    ret = np.diff(np.log(close), axis=0)
    corr = np.corrcoef(ret.T)
    off = corr[~np.eye(4, dtype=bool)]
    # equicorrelated mixing at rho=0.6: every pair lands near it
    assert float(off.min()) > 0.35, corr
    assert float(off.max()) < 0.85, corr


# ----------------------------------------------------------------------
# determinism


def test_generate_bitwise_deterministic_and_seed_sensitive():
    p = scenario_params("regime_mix")
    a = generate(p, jax.random.PRNGKey(7), 256)
    b = generate(p, jax.random.PRNGKey(7), 256)
    for fa, fb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
    c = generate(p, jax.random.PRNGKey(8), 256)
    assert not np.array_equal(np.asarray(a.close), np.asarray(c.close))


def test_subprocess_bitwise_determinism_same_seed_same_frame():
    """Satellite pin: same seed + preset => bitwise-identical frames
    across two fresh processes (threefry is backend- and process-stable;
    the compile cache is the suite's fresh per-session dir)."""
    script = (
        "import hashlib, sys\n"
        "from gymfx_tpu.scengen.feed import synthesize_frame\n"
        "df, flags = synthesize_frame({'scengen_preset': 'flash_crash',"
        " 'scengen_bars': 256, 'scengen_seed': 11, 'timeframe': 'M1'})\n"
        "h = hashlib.sha256()\n"
        "h.update(df.to_numpy().tobytes())\n"
        "h.update(flags.tobytes())\n"
        "print(h.hexdigest())\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    digests = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-c", script], cwd=str(REPO), env=env,
            capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        digests.append(proc.stdout.strip().splitlines()[-1])
    assert digests[0] == digests[1], digests


# ----------------------------------------------------------------------
# the FX calendar grid


def test_fx_timestamp_grid_skips_weekends_and_marks_mondays():
    idx, monday = fx_timestamp_grid(512, 1.0)
    assert len(idx) == 512 and monday.shape == (512,)
    hours = idx.dayofweek * 24 + idx.hour
    # closed window: Fri 22:00 UTC through Sun 22:00 UTC
    assert not np.any((hours >= 4 * 24 + 22) & (hours < 6 * 24 + 22))
    # monday_open marks exactly the first bar after each weekend gap
    step = (idx[1:] - idx[:-1]).to_numpy()
    gap_after = np.concatenate([[False], step > step.min()])
    np.testing.assert_array_equal(monday, gap_after)
    assert monday.sum() >= 2  # 512 hourly bars span multiple weekends


# ----------------------------------------------------------------------
# dataset + env wiring


def test_scengen_dataset_flags_channel_and_slicing():
    config = dict(DEFAULT_VALUES)
    config.update(feed="scengen", scengen_preset="liquidity_drought",
                  scengen_bars=300, scengen_seed=5, window_size=8)
    ds = ScenGenDataset(config)
    assert len(ds) == 300 and ds.scen_flags.shape == (300,)
    md = ds.build_market_data(window_size=8, device=False)
    np.testing.assert_array_equal(np.asarray(md.scen_flags), ds.scen_flags)
    assert np.any(ds.scen_flags & FLAG_DROUGHT != 0)
    # chronological slice keeps frame and flags aligned
    tail = ds.sliced(slice(100, 260))
    assert len(tail) == 160
    np.testing.assert_array_equal(tail.scen_flags, ds.scen_flags[100:260])
    assert tail.dataframe.index.equals(ds.dataframe.index[100:260])


def test_replay_path_identical_with_feed_key_unset():
    """The bitwise-identity pin: adding the feed knob must not perturb
    the replay path — a config that never mentions ``feed`` and one
    pinning ``feed=replay`` build the same data and the same episode."""
    base = dict(DEFAULT_VALUES)
    base.update(window_size=8, max_rows=120, num_envs=1)
    cfg_unset = dict(base)
    cfg_unset.pop("feed")
    env_a = Environment(cfg_unset)
    env_b = Environment(dict(base, feed="replay"))
    assert env_a.cfg.lob_flow_from_scengen is False
    # replay tapes carry an all-zero flags channel
    assert np.all(np.asarray(env_a.data.scen_flags) == 0)
    _, out_a = rollout(env_a.cfg, env_a.params, env_a.data,
                       buy_hold_driver(), 64, jax.random.PRNGKey(0))
    _, out_b = rollout(env_b.cfg, env_b.params, env_b.data,
                       buy_hold_driver(), 64, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(
        np.asarray(out_a["equity_delta"]), np.asarray(out_b["equity_delta"])
    )
    np.testing.assert_array_equal(
        np.asarray(out_a["action"]), np.asarray(out_b["action"])
    )


def test_feed_knob_is_honor_or_reject():
    with pytest.raises(ValueError, match="feed"):
        Environment(dict(DEFAULT_VALUES, feed="telepathy"))
    with pytest.raises(ValueError, match="preset"):
        Environment(dict(DEFAULT_VALUES, feed="scengen",
                         scengen_preset="bogus"))


def test_eval_split_on_generated_feed_splits_one_generation():
    """eval_split on feed=scengen slices ONE generated tape (train head,
    eval tail) — generating per-half would desync the hazard overlays."""
    from gymfx_tpu.train.common import build_train_eval_envs

    config = dict(DEFAULT_VALUES)
    config.update(feed="scengen", scengen_preset="flash_crash",
                  scengen_bars=240, scengen_seed=3, window_size=8,
                  num_envs=4, eval_split=0.25,
                  save_config=None, results_file=None)
    tr_env, ev_env = build_train_eval_envs(config)
    assert tr_env.n_bars == 180 and ev_env.n_bars == 60
    full = ScenGenDataset(config)  # deterministic: regenerates the tape
    np.testing.assert_array_equal(
        np.asarray(tr_env.dataset.scen_flags), full.scen_flags[:180]
    )
    np.testing.assert_array_equal(
        np.asarray(ev_env.dataset.scen_flags), full.scen_flags[180:]
    )
    assert (
        tr_env.dataset.timestamps.iloc[-1] < ev_env.dataset.timestamps.iloc[0]
    )


# ----------------------------------------------------------------------
# PPO end-to-end across presets (acceptance: >= 3 presets)


def test_ppo_trains_on_three_scengen_presets():
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from

    for preset in ("regime_mix", "flash_crash", "liquidity_drought"):
        config = dict(DEFAULT_VALUES)
        # identical shapes across presets: the episode/update programs
        # compile once and the next presets reuse the cache
        config.update(feed="scengen", scengen_preset=preset,
                      scengen_bars=160, scengen_seed=1, window_size=8,
                      num_envs=4, ppo_horizon=8, ppo_epochs=1,
                      ppo_minibatches=2, policy_kwargs={"hidden": [16]})
        env = Environment(config)
        tr = PPOTrainer(env, ppo_config_from(config))
        s = tr.init_state(0)
        for _ in range(2):
            s, metrics = tr.train_step(s)
        assert np.isfinite(float(metrics["loss"])), preset
        assert np.isfinite(float(metrics["entropy"])), preset


# ----------------------------------------------------------------------
# LOB flow coupling (satellite: crash in the tape => crash in the flow)


def test_lob_flow_params_follow_tape_flags():
    import jax.numpy as jnp

    from gymfx_tpu.lob.scenarios import (
        flow_params_from_regime,
        scenario_flow_params,
    )

    base = scenario_flow_params("lob_calm")
    thin = scenario_flow_params("lob_thin")
    flash = scenario_flow_params("lob_flash_crash")
    n_msgs = 64

    calm = flow_params_from_regime(base, jnp.int32(0), n_msgs)
    for got, want in zip(calm, base):
        np.testing.assert_allclose(np.asarray(got), want)

    crash = flow_params_from_regime(base, jnp.int32(FLAG_CRASH), n_msgs)
    assert int(crash.crash_at) == n_msgs // 3
    assert int(crash.crash_len) == max(1, n_msgs // 8)
    assert int(crash.crash_qty) == flash.crash_qty

    drought = flow_params_from_regime(base, jnp.int32(FLAG_DROUGHT), n_msgs)
    np.testing.assert_allclose(float(drought.p_noop), thin.p_noop)
    np.testing.assert_allclose(float(drought.base_qty), thin.base_qty)
    np.testing.assert_allclose(float(drought.seed_qty), thin.seed_qty)
    # a drought alone never arms the forced-sell burst
    np.testing.assert_allclose(float(drought.crash_qty), base.crash_qty)


def test_lob_venue_on_scengen_feed_consistent_with_tape():
    """feed=scengen + venue=lob: every crash bar in the generated tape
    arms the flow burst (the consistency contract), and the episode
    stays finite under the per-bar FlowParams blending."""
    config = dict(DEFAULT_VALUES)
    # seed 3 is pinned to put a crash window inside the 160-bar tape
    config.update(feed="scengen", scengen_preset="flash_crash",
                  scengen_bars=160, scengen_seed=3, window_size=8,
                  venue="lob", lob_messages_per_bar=32)
    env = Environment(config)
    assert env.cfg.lob_flow_from_scengen is True
    flags = np.asarray(env.dataset.scen_flags)
    assert np.any(flags & FLAG_CRASH != 0)  # the tape really crashed
    _, out = rollout(env.cfg, env.params, env.data, buy_hold_driver(), 100,
                     jax.random.PRNGKey(0))
    assert np.all(np.isfinite(np.asarray(out["equity_delta"])))
    # the oracle replay cross-check refuses this config loudly: its
    # bar-level oracle cannot model per-bar flow params
    from gymfx_tpu.simulation.crosscheck import crosscheck_lob_episode

    with pytest.raises(ValueError, match="scengen"):
        crosscheck_lob_episode(config, steps=20, env=env)


# ----------------------------------------------------------------------
# fault-profile stress overlay on a REPLAYED tape


def test_fault_profile_scengen_clause_stresses_replay_tape():
    from gymfx_tpu.resilience.faults import (
        apply_fault_profile_to_market_data,
        parse_fault_profile,
    )

    config = dict(DEFAULT_VALUES)
    config.update(window_size=8, max_rows=120)
    env = Environment(config)
    data = env.dataset.build_market_data(window_size=8, device=False)
    profile = parse_fault_profile("scengen=liquidity_drought;seed=5")
    assert profile["scengen"] == "liquidity_drought"
    stressed = apply_fault_profile_to_market_data(data, profile)
    flags = np.asarray(stressed.scen_flags)
    assert np.any(flags & FLAG_DROUGHT != 0)
    hit = flags & FLAG_DROUGHT != 0
    p = scenario_params("liquidity_drought")
    assert float(np.asarray(stressed.ev_spread_mult)[hit].min()) >= float(
        np.asarray(data.ev_spread_mult)[hit].min() * p.drought_spread
    ) - 1e-6
    # untouched bars stay bitwise identical
    np.testing.assert_array_equal(
        np.asarray(stressed.close)[~hit & (flags == 0)],
        np.asarray(data.close)[~hit & (flags == 0)],
    )
    # the padded tail mirrors the stressed closes (window reads agree)
    w = np.asarray(stressed.padded_close).shape[0] - flags.shape[0]
    np.testing.assert_allclose(
        np.asarray(stressed.padded_close)[w:], np.asarray(stressed.close),
        rtol=1e-6,
    )
    with pytest.raises(ValueError, match="preset"):
        parse_fault_profile("scengen=bogus")


# ----------------------------------------------------------------------
# scenario gate report (schema-pinned)


def test_scenario_gate_quick_report_is_schema_valid():
    report = run_gate(presets=["regime_mix"], n_bars=192, seed=0,
                      serving_ticks=4)
    assert validate_report(report) == []
    assert report["kind"] == "scenario_gate_report"
    row = report["scenarios"]["regime_mix"]
    assert row["finite"] and row["passed"]
    serving = report["serving"]
    assert serving["decisions"] == serving["ticks"] == 4
    assert serving["fallback_count"] == 1 and serving["fallback_tagged"]
    assert serving["late_compiles"] == 0
    assert report["passed"] is True
    # JSON-serializable end to end (the report is written to disk in CI)
    json.loads(json.dumps(report))


def test_validate_report_rejects_drifted_reports():
    bad = {"kind": "scenario_gate_report", "scenarios": {"x": {}},
           "serving": {}}
    problems = validate_report(bad)
    assert any("missing required key" in p for p in problems)
    assert any("scenario 'x'" in p for p in problems)
    assert any("serving" in p for p in problems)
    assert validate_report([]) != []


def test_preset_registry_is_closed():
    names = preset_names()
    assert len(names) >= 8 and names == tuple(sorted(names))
    with pytest.raises(ValueError, match="preset"):
        scenario_params("not_a_preset")
