"""Superstep driver (docs/performance.md): ``train_many(state, K)``
fuses K train steps into one donated lax.scan dispatch with metrics
stacked on device.  The contract under test is BIT-IDENTITY — the fused
trajectory (params, opt state, env batch, RNG, guard counters) must
match K sequential ``train_step`` calls exactly, including under an
injected NaN fault, and superstep-boundary checkpoints must resume
bit-identically."""
import numpy as np
import pytest

from gymfx_tpu.config import DEFAULT_VALUES
from gymfx_tpu.core.runtime import Environment
from gymfx_tpu.data.feed import MarketDataset
from gymfx_tpu.resilience.faults import (
    SimulatedPreemptionError,
    contaminate_market_data,
)
from tests.helpers import uptrend_df

K = 4


def _env(**over):
    config = dict(DEFAULT_VALUES)
    config.update(window_size=8, timeframe="M1", num_envs=4, ppo_horizon=16,
                  ppo_epochs=2, ppo_minibatches=2,
                  policy_kwargs={"hidden": [16, 16]})
    config.update(over)
    return Environment(config, dataset=MarketDataset(uptrend_df(120), config)), config


def _ppo(**over):
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from

    env, config = _env(**over)
    return PPOTrainer(env, ppo_config_from(config)), env


def _impala(**over):
    from gymfx_tpu.train.impala import ImpalaTrainer, impala_config_from

    over.setdefault("impala_unroll", 16)
    over.setdefault("policy", "mlp")
    over.setdefault("policy_kwargs", {})
    env, config = _env(**over)
    return ImpalaTrainer(env, impala_config_from(config)), env


def _assert_state_equal(a, b, what):
    import jax

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), what
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"{what} leaf {i}"
        )


def _run_both(tr, k=K):
    """K sequential donated train_step calls vs ONE train_many(·, k)
    dispatch from an identical initial state (init_state is
    deterministic; two independent states because both paths donate)."""
    s_seq = tr.init_state(0)
    s_fused = tr.init_state(0)
    per_step = []
    for _ in range(k):
        s_seq, m = tr.train_step(s_seq)
        per_step.append({key: np.asarray(v).copy() for key, v in m.items()})
    s_many, stacked = tr.train_many(s_fused, k)
    return s_seq, per_step, s_many, stacked


def _assert_metrics_match(per_step, stacked, k=K):
    assert set(per_step[0]) == set(stacked)
    for key, arr in stacked.items():
        arr = np.asarray(arr)
        assert arr.shape[0] == k, key
        for j in range(k):
            np.testing.assert_array_equal(
                arr[j], per_step[j][key], err_msg=f"{key} step {j}"
            )


def test_ppo_train_many_bit_identical_to_sequential():
    tr, _ = _ppo()
    s_seq, per_step, s_many, stacked = _run_both(tr)
    # full TrainState: params + opt_state + env batch + obs + RNG
    _assert_state_equal(s_seq, s_many, "ppo state")
    _assert_metrics_match(per_step, stacked)


def test_impala_train_many_bit_identical_to_sequential():
    tr, _ = _impala()
    s_seq, per_step, s_many, stacked = _run_both(tr)
    _assert_state_equal(s_seq, s_many, "impala state")
    _assert_metrics_match(per_step, stacked)


def test_ppo_superstep_guard_counters_identical_under_nan_fault():
    """The stacked guard counters ARE the watchdog's input: under a
    NaN-contaminated feed the fused path must reproduce the per-step
    nonfinite_skips / poisoned_env_resets trajectory exactly."""
    tr, env = _ppo()
    env.data = contaminate_market_data(env.data, bars=[30, 31])
    k = 6  # enough steps for the poisoned bars to cross a rollout
    s_seq, per_step, s_many, stacked = _run_both(tr, k=k)
    _assert_state_equal(s_seq, s_many, "ppo state (nan fault)")
    _assert_metrics_match(per_step, stacked, k=k)
    # the fault actually fired — this test must not pass vacuously
    assert float(np.sum(np.asarray(stacked["nonfinite_skips"]))) > 0


def test_ppo_train_loop_superstepped_matches_per_step_dispatch():
    """End to end through PPOTrainer.train: same seed, K=2 vs K=1 —
    final params bit-identical (DelayedLogger + ResilientLoop included
    in the loop under test)."""
    import jax

    tr, _ = _ppo()
    total = 4 * 16 * 4  # 4 iterations
    s_ref, m_ref = tr.train(total, seed=3)
    ref_leaves = [np.asarray(x).copy() for x in jax.tree.leaves(s_ref.params)]
    s_k2, m_k2 = tr.train(total, seed=3, supersteps_per_dispatch=2)
    for i, (a, b) in enumerate(zip(ref_leaves, jax.tree.leaves(s_k2.params))):
        np.testing.assert_array_equal(a, np.asarray(b), err_msg=f"leaf {i}")
    assert m_ref["iterations"] == m_k2["iterations"] == 4


@pytest.mark.slow
def test_superstep_checkpoint_resume_bit_identical(tmp_path):
    """Preempt a K=2 run at a superstep boundary, resume from the
    boundary auto-checkpoint, land on the SAME final params as an
    uninterrupted K=1 run (issue acceptance: resume from a superstep
    boundary is bit-identical)."""
    import jax

    from gymfx_tpu.train.checkpoint import load_checkpoint

    # the triple-run shape is what segfaults deserializing from the warm
    # persistent compile cache — opt out like the K=1 preempt drill
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        tr, _ = _ppo()
        spi = 4 * 16
        total = spi * 4
        s_ref, _ = tr.train(total, seed=3)
        ref_leaves = [
            np.asarray(x).copy() for x in jax.tree.leaves(s_ref.params)
        ]
        with pytest.raises(SimulatedPreemptionError):
            tr.train(total, seed=3, supersteps_per_dispatch=2,
                     checkpoint_dir=str(tmp_path), checkpoint_every=2,
                     preempt_at=2)
        template = tr.init_state(3)
        state, step = load_checkpoint(str(tmp_path), template=template)
        assert step == 2 * spi  # the boundary checkpoint, iters [0, 2)
        s_res, _ = tr.train(
            total - step, seed=3, initial_state=state, step_offset=step,
            supersteps_per_dispatch=2,
        )
        for i, (a, b) in enumerate(
            zip(ref_leaves, jax.tree.leaves(s_res.params))
        ):
            np.testing.assert_array_equal(
                a, np.asarray(b), err_msg=f"leaf {i}"
            )
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


# ---------------------------------------------------------------------------
# host-side superstep semantics (no jax): ResilientLoop + DelayedLogger
# ---------------------------------------------------------------------------
def test_resilient_loop_superstep_checkpoints_on_boundary_crossing(tmp_path):
    from gymfx_tpu.resilience.loop import ResilientLoop

    saved = []
    loop = ResilientLoop(steps_per_iter=10, checkpoint_every=3,
                         checkpoint_dir=str(tmp_path),
                         max_consecutive_skips=0)
    loop._save = lambda state_fn, step: saved.append(step)
    state_fn = lambda: ({}, {})
    loop.after_superstep(0, 2, {}, state_fn)   # it_end=2: no multiple of 3
    loop.after_superstep(2, 2, {}, state_fn)   # it_end=4: crossed 3
    loop.after_superstep(4, 2, {}, state_fn)   # it_end=6: crossed 6
    assert saved == [40, 60]  # step ids stay it_end * steps_per_iter


def test_resilient_loop_superstep_watchdog_replays_stacked_counters():
    """Stacked (k,) guard counters replay per-iteration: divergence
    aborts at the same ITERATION as the per-step loop, detected one
    superstep (one delayed fetch) later."""
    from gymfx_tpu.resilience.guards import NonFiniteDivergenceError
    from gymfx_tpu.resilience.loop import ResilientLoop

    full = np.array([1.0, 1.0])
    stacked = {"nonfinite_skips": full, "guard_updates": full}
    state_fn = lambda: ({}, {})
    loop = ResilientLoop(steps_per_iter=10, max_consecutive_skips=2)
    loop.after_superstep(0, 2, stacked, state_fn)  # held (delayed fetch)
    with pytest.raises(NonFiniteDivergenceError):
        loop.after_superstep(2, 2, stacked, state_fn)
    # same limit, per-step: aborts once iterations 0 and 1 are seen
    loop2 = ResilientLoop(steps_per_iter=10, max_consecutive_skips=2)
    one = {"nonfinite_skips": 1.0, "guard_updates": 1.0}
    loop2.after_step(0, one, state_fn)
    loop2.after_step(1, one, state_fn)
    with pytest.raises(NonFiniteDivergenceError):
        loop2.after_step(2, one, state_fn)


def test_resilient_loop_superstep_preempts_on_first_boundary():
    from gymfx_tpu.resilience.loop import ResilientLoop

    loop = ResilientLoop(steps_per_iter=10, max_consecutive_skips=0,
                         preempt_at=3)
    state_fn = lambda: ({}, {})
    loop.after_superstep(0, 2, {}, state_fn)  # it_end=2 < 3
    with pytest.raises(SimulatedPreemptionError):
        loop.after_superstep(2, 2, {}, state_fn)  # it_end=4 >= 3


def test_delayed_logger_flushes_one_dispatch_late(capsys):
    """log_every snapshots are held as-is and stringified one dispatch
    later, so logging never forces a host sync on the logged iteration;
    finish() flushes the tail."""
    from gymfx_tpu.telemetry import DelayedLogger

    logger = DelayedLogger("t", log_every=2, iters=4)
    logger.after_dispatch(0, 1, {"loss": 1.0})
    logger.after_dispatch(1, 1, {"loss": 2.0})   # crosses 2: held
    assert capsys.readouterr().out == ""          # not printed yet
    logger.after_dispatch(2, 1, {"loss": 3.0})   # flushes iter 2's snap
    assert "iter 2/4" in capsys.readouterr().out
    logger.after_dispatch(3, 1, {"loss": 4.0})   # crosses 4: held
    logger.finish()
    assert "iter 4/4" in capsys.readouterr().out


def test_delayed_logger_silent_when_disabled(capsys):
    from gymfx_tpu.telemetry import DelayedLogger

    logger = DelayedLogger("t", log_every=0, iters=4)
    for it in range(4):
        logger.after_dispatch(it, 1, {"loss": float(it)})
    logger.finish()
    assert capsys.readouterr().out == ""
