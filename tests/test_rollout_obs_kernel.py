"""Rollout parity for the fused per-step obs kernel (r6).

``rollout_obs_kernel`` swaps the feature-scaling op inside the env
step — nothing else — so a full training rollout under the kernel must
be BITWISE identical to the plain-XLA rollout: same trajectories, same
env states, same policy outputs, for every policy family on the
rollout hot path.  Runs the pallas kernel in interpret mode so the
parity gate holds on CPU CI; on-chip the same oracle relationship is
what makes the XLA path the fallback/debug twin.
"""
import jax
import numpy as np
import pytest

from gymfx_tpu.config import DEFAULT_VALUES
from gymfx_tpu.core.runtime import Environment
from gymfx_tpu.data.feed import MarketDataset
from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from

from helpers import make_df


def _df(n=160, seed=0):
    rng = np.random.default_rng(seed)
    closes = 1.1 * np.exp(np.cumsum(rng.normal(0, 2e-4, n)))
    ret1 = np.concatenate([[0.0], np.diff(np.log(closes))])
    return make_df(closes, highs=closes + 5e-5, lows=closes - 5e-5,
                   extra={"RET1": ret1})


def _trainer(policy, kernel_mode):
    config = dict(DEFAULT_VALUES)
    config.update(
        window_size=8, timeframe="M1", num_envs=4,
        ppo_horizon=8, ppo_epochs=1, ppo_minibatches=2,
        policy=policy,
        feature_columns=["CLOSE", "RET1"],
        feature_scaling="rolling_zscore", feature_scaling_window=16,
        rollout_obs_kernel=kernel_mode,
    )
    env = Environment(config, dataset=MarketDataset(_df(), config))
    return PPOTrainer(env, ppo_config_from(config))


def _tree_equal(a, b, label):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), label
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"{label}: leaf {i}"
        )


@pytest.mark.parametrize("policy", ["mlp", "lstm", "transformer"])
def test_kernel_rollout_bitwise_matches_xla_rollout(policy):
    t_xla = _trainer(policy, "off")
    t_ker = _trainer(policy, "interpret")

    s_xla = t_xla.init_state(0)
    s_ker = t_ker.init_state(0)
    # reset obs (built through the dispatch) already identical
    _tree_equal(s_xla.obs_vec, s_ker.obs_vec, f"{policy} reset obs")

    out_xla = t_xla._rollout(
        s_xla.params, s_xla.env_states, s_xla.obs_vec,
        s_xla.policy_carry, s_xla.rng,
    )
    out_ker = t_ker._rollout(
        s_ker.params, s_ker.env_states, s_ker.obs_vec,
        s_ker.policy_carry, s_ker.rng,
    )
    # (env_states, obs_vec, carry, rng, traj, last_value) — all of it
    _tree_equal(out_xla, out_ker, f"{policy} rollout")


def test_kernel_train_step_bitwise_matches_xla(policy="mlp"):
    """One full jitted train step (rollout + update) stays bitwise
    identical: the stored trajectories feed the update, so any obs
    divergence would surface in the new params."""
    t_xla = _trainer(policy, "off")
    t_ker = _trainer(policy, "interpret")
    s_xla, _ = t_xla.train_step(t_xla.init_state(0))
    s_ker, _ = t_ker.train_step(t_ker.init_state(0))
    _tree_equal(s_xla.params, s_ker.params, "params after train step")


def test_rollout_obs_kernel_knob_validation():
    from gymfx_tpu.core.types import make_env_config

    config = dict(DEFAULT_VALUES)
    config.update(window_size=8, rollout_obs_kernel="sideways")
    with pytest.raises(ValueError, match="rollout_obs_kernel"):
        make_env_config(config, n_bars=64, n_features=2)


@pytest.mark.parametrize("mode", ["on", "interpret"])
def test_rollout_obs_kernel_refused_without_feature_columns(mode):
    """Honor-or-reject: with no feature columns there is no feature
    window to scale, and core/obs.build_obs would never reach the
    kernel — the switch is refused instead of accepted and ignored
    (what bench.py's flagship did until PR 22)."""
    from gymfx_tpu.core.types import make_env_config

    config = dict(DEFAULT_VALUES)
    config.update(window_size=8, rollout_obs_kernel=mode)
    with pytest.raises(ValueError, match="n_features > 0"):
        make_env_config(config, n_bars=64, n_features=0)
    assert make_env_config(
        config, n_bars=64, n_features=2
    ).rollout_obs_kernel == mode
