"""The one host loop and the one from-config entry (train/loop.py), held
to the same behaviour over the three step-program trainers.

Every case runs for ``ppo``, ``impala`` and ``portfolio`` at 8 envs x 8
steps with an MLP: what ``train`` computes against hand-dispatched train
steps, the preempt/resume drill through the from-config entry, the warm
start, what a telemetry bundle records, the summaries' keys, and K = 2
against K = 1 where the trainer has supersteps.
"""
import json

import jax
import numpy as np
import pytest

from gymfx_tpu.config import DEFAULT_VALUES
from gymfx_tpu.resilience.faults import SimulatedPreemptionError
from gymfx_tpu.telemetry import telemetry_from_config
from gymfx_tpu.telemetry.ledger import read_ledger
from gymfx_tpu.train import impala, portfolio_ppo, ppo
from gymfx_tpu.train.checkpoint import load_train_state

FAMILIES = ("ppo", "impala", "portfolio")
SPECS = {"ppo": ppo.SPEC, "impala": impala.SPEC, "portfolio": portfolio_ppo.SPEC}
ALGOS = {"ppo": "ppo", "impala": "impala", "portfolio": "portfolio_ppo"}
ENTRIES = {
    "ppo": ppo.train_from_config,
    "impala": impala.train_impala_from_config,
    "portfolio": portfolio_ppo.train_portfolio_from_config,
}
SPI = 64  # 8 envs x 8 steps
SEED = 5

_SINGLE = dict(
    input_data_file="examples/data/eurusd_uptrend.csv", window_size=8,
    num_envs=8, ppo_horizon=8, impala_unroll=8, ppo_epochs=1,
    ppo_minibatches=2, policy="mlp", policy_kwargs={"hidden": [16, 16]},
    max_rows=129,  # 128 evaluation steps: whole 64-step chunks, one program
)
_PORTFOLIO = dict(
    portfolio_files={"EUR_USD": "examples/data/eurusd_sample.csv",
                     "GBP_USD": "examples/data/gbpusd_sample.csv"},
    window_size=8, initial_cash=10000.0, num_envs=8, ppo_horizon=8,
    ppo_epochs=1, ppo_minibatches=2, policy="mlp", max_rows=129,
)

# the summaries' keys as the three separate entries gave them before they
# became one (a run of the parent commit: preempted at 2, resumed to 4)
_EVAL_KEYS = {
    "avg_trade_pnl", "eval_scope", "final_equity", "initial_cash",
    "max_drawdown_fraction", "max_drawdown_money", "max_drawdown_pct",
    "metric_schema", "rap", "risk_adjusted_total_return",
    "risk_penalty_lambda", "sharpe_ratio", "sharpe_ratio_steps", "sqn",
    "total_return", "trades_lost", "trades_total", "trades_won",
    "train_metrics", "checkpoint_dir",
}
_LOOP_KEYS = {"env_steps_per_sec", "iterations", "total_env_steps",
              "last_checkpoint_step"}
_STEP_KEYS = {"entropy", "loss", "mean_reward", "policy_loss", "value_loss"}
_GUARD_KEYS = {"guard_updates", "nonfinite_skips", "poisoned_env_resets",
               "mean_episode_done"}
SUMMARY_KEYS = {
    "ppo": (_EVAL_KEYS, _LOOP_KEYS | _STEP_KEYS | _GUARD_KEYS),
    "impala": (_EVAL_KEYS, _LOOP_KEYS | _STEP_KEYS | _GUARD_KEYS | {"mean_rho"}),
    "portfolio": (_EVAL_KEYS | {"mode", "pairs", "trainer"},
                  _LOOP_KEYS | _STEP_KEYS),
}


@pytest.fixture(autouse=True)
def _no_persistent_compile_cache_for_the_portfolio(request):
    """The portfolio's programs are not read back from a warm persistent
    compile cache on the CPU backend (tests/test_portfolio.py)."""
    callspec = getattr(request.node, "callspec", None)
    off = callspec is not None and callspec.params.get("family") == "portfolio"
    if off:
        jax.config.update("jax_enable_compilation_cache", False)
    yield
    if off:
        jax.config.update("jax_enable_compilation_cache", True)


def config_of(family, **over):
    config = dict(DEFAULT_VALUES)
    config.update(_PORTFOLIO if family == "portfolio" else _SINGLE)
    config.update(mode="training", quiet_mode=True, seed=SEED, **over)
    return config


def host(tree):
    return [np.asarray(x) for x in jax.tree.leaves(jax.device_get(tree))]


def assert_bitwise(got, want):
    got, want = host(got), host(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


@pytest.fixture(scope="module")
def built():
    """family -> its trainer, built as the from-config entry builds it."""
    cache = {}

    def get(family):
        if family not in cache:
            spec, config = SPECS[family], config_of(family)
            env, _eval_env = spec.build_envs(config)
            cache[family] = spec.trainer_cls(env, spec.config_from(config))
        return cache[family]

    return get


@pytest.fixture(scope="module")
def four_iterations(built):
    """family -> (state, metrics) of an uninterrupted ``train`` of four."""
    cache = {}

    def get(family):
        if family not in cache:
            state, metrics = built(family).train(4 * SPI, seed=SEED)
            cache[family] = (host(state), metrics)
        return cache[family]

    return get


@pytest.fixture(scope="module")
def resumed(built, tmp_path_factory):
    """family -> (summary, restored state, step) of a run preempted at
    iteration 2 with a checkpoint every iteration, then resumed to 4
    through the from-config entry."""
    cache = {}

    def get(family):
        if family not in cache:
            trainer, spec = built(family), SPECS[family]
            ckpt = str(tmp_path_factory.mktemp(f"resume_{family}"))
            tcfg = getattr(trainer, "icfg", None) or trainer.pcfg
            with pytest.raises(SimulatedPreemptionError):
                trainer.train(
                    4 * SPI, seed=SEED, checkpoint_dir=ckpt, checkpoint_every=1,
                    checkpoint_metadata=spec.checkpoint_metadata(tcfg, trainer.env),
                    preempt_at=2)
            summary = ENTRIES[family](config_of(
                family, train_total_steps=2 * SPI, resume_training=True,
                checkpoint_dir=ckpt, checkpoint_every=1))
            state, _params, step = load_train_state(ckpt, trainer, spec.state_cls)
            cache[family] = (summary, state, step)
        return cache[family]

    return get


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("family", FAMILIES)
def test_train_is_init_state_and_the_train_steps(built, family):
    trainer = built(family)
    want = trainer.init_state(SEED)
    for _ in range(3):
        want, _metrics = trainer.train_step(want)
    got, metrics = trainer.train(3 * SPI, seed=SEED)
    assert_bitwise(got, want)
    assert metrics["iterations"] == 3
    assert metrics["total_env_steps"] == 3 * SPI == 3 * trainer.steps_per_iter
    assert "last_checkpoint_step" not in metrics


@pytest.mark.parametrize("family", FAMILIES)
def test_preempted_and_resumed_ends_where_the_uninterrupted_run_ends(
        built, four_iterations, resumed, family):
    _summary, state, step = resumed(family)
    assert step == 4 * SPI
    want, _metrics = four_iterations(family)
    assert_bitwise(state, want)


@pytest.mark.parametrize("family", FAMILIES)
def test_entry_summaries_hold_the_keys_they_held(resumed, family):
    summary, _state, _step = resumed(family)
    keys, train_keys = SUMMARY_KEYS[family]
    assert set(summary) == keys
    assert set(summary["train_metrics"]) == train_keys
    assert summary["train_metrics"]["last_checkpoint_step"] == 4 * SPI
    assert summary["eval_scope"] == "in_sample"


@pytest.mark.parametrize("family", FAMILIES)
def test_initial_params_land_where_learner_params_reads(built, family):
    trainer = built(family)

    def warm():  # fresh buffers each time: the step donates its state
        other = trainer.learner_params(trainer.init_state(SEED + 1))
        return jax.tree.map(lambda x: x * 0.5, other)

    fresh = trainer.init_state(SEED)
    if family == "impala":
        want = fresh._replace(learner_params=warm(), actor_params=warm())
    else:
        want = fresh._replace(params=warm())
    want, _metrics = trainer.train_step(want)
    got, _metrics = trainer.train(SPI, seed=SEED, initial_params=warm())
    assert_bitwise(got, want)
    assert_bitwise(trainer.learner_params(got), trainer.learner_params(want))
    if family == "impala":
        # one update of four before a sync: the actors still hold the copy
        assert_bitwise(got.actor_params, warm())


@pytest.mark.parametrize("family", FAMILIES)
def test_telemetry_records_the_same_rows_for_every_trainer(
        built, tmp_path, family):
    trainer = built(family)
    bundle = telemetry_from_config({
        "telemetry_ledger": str(tmp_path / "ledger.jsonl"),
        "telemetry_jsonl": str(tmp_path / "sink.jsonl"),
        "telemetry_spans": True,
    })
    try:
        trainer.train(3 * SPI, seed=SEED, telemetry=bundle,
                      checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=1)
    finally:
        bundle.close()
    kinds = [row["kind"] for row in read_ledger(str(tmp_path / "ledger.jsonl"))]
    assert kinds == (["run_start"]
                     + ["superstep_dispatch", "checkpoint_write"] * 3
                     + ["run_end"])
    rows = [json.loads(line) for line in
            (tmp_path / "sink.jsonl").read_text().splitlines() if line.strip()]
    spans = [r for r in rows if r["kind"] == "span"]
    assert [(s["span"], s["attrs"]) for s in spans] == [
        ("train/superstep", {"algo": ALGOS[family], "it": it, "k": 1})
        for it in range(3)]
    drained = [r for r in rows if r["kind"] == "train_metrics"]
    assert [(r["algo"], r["iter"]) for r in drained] == [
        (ALGOS[family], it) for it in (1, 2, 3)]
    assert trainer.ALGO == ALGOS[family]


@pytest.mark.parametrize("family", ["ppo", "impala"])
def test_two_supersteps_a_dispatch_equal_one(built, four_iterations, family):
    want, want_metrics = four_iterations(family)
    got, metrics = built(family).train(
        4 * SPI, seed=SEED, supersteps_per_dispatch=2)
    assert_bitwise(got, want)
    wall = "env_steps_per_sec"
    assert ({k: v for k, v in metrics.items() if k != wall}
            == {k: v for k, v in want_metrics.items() if k != wall})


def test_cli_picks_the_spec_by_trainer(monkeypatch):
    """``app/main.run_mode`` hands ``train_entry`` the named trainer's spec
    (PPO's where the name is not one of the table's)."""
    from gymfx_tpu.app import main
    from gymfx_tpu.train import loop

    names, seen = ("ppo", "impala", "portfolio", "PPO", "other"), []
    monkeypatch.setattr(loop, "train_entry",
                        lambda config, spec: seen.append(spec) or {})
    for name in names:
        main.run_mode({"mode": "training", "trainer": name})
    assert all(got is SPECS.get(name.lower(), ppo.SPEC)
               for name, got in zip(names, seen)) and len(seen) == len(names)
