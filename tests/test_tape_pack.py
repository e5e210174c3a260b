"""The env step reads the tape one packed row per bar index
(gymfx_tpu/data/feed.py: ``BAR_COLUMNS``, ``pack_bars``, ``read_bar``).

  * parity: for every producer of a ``MarketData`` the packed lookup at
    the step's three index expressions returns, bit for bit, what the
    per-column reads return — negative ``minute_of_week``, a rebased shard
    (``row0 > 0``) and the last bar (the ``n - 1`` clamp) included;
  * trajectory: 16 envs x 8 steps of ``env_core.step`` give bitwise the
    trajectory of an oracle that reads the columns one by one.  The oracle
    lives here, not in the package;
  * structure (the mechanism's counter): the flagship's compiled step holds
    at most one ``gather`` per distinct bar index under
    ``rollout/env_step/tape_read``, and no gather of a 1-D tape column is
    left under ``rollout/env_step``.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from gymfx_tpu.config import DEFAULT_VALUES
from gymfx_tpu.core import env as env_core
from gymfx_tpu.core import obs as obs_mod
from gymfx_tpu.data import compress as C
from gymfx_tpu.data.feed import (
    BAR_COLUMNS,
    MarketDataset,
    pack_bars,
    read_bar,
    shard_market_data,
)
from gymfx_tpu.telemetry import scopes
from tests.helpers import make_df, make_env

WINDOW = 8


def column_reads(data, index):
    """THE ORACLE: every field read from its own column, one by one, as the
    step read the tape before the packed table."""
    return {f: getattr(data, f)[index - data.row0] for f in BAR_COLUMNS}


def noisy_df(n, seed=0):
    """Distinct o/h/l/c, event columns that are not constant, and a few
    unparseable timestamps (``minute_of_week`` -1 there)."""
    rng = np.random.default_rng(seed)
    closes = 1.1 * np.exp(np.cumsum(rng.normal(0, 4e-4, n)))
    opens = closes * (1 + rng.normal(0, 1e-4, n))
    df = make_df(
        closes, opens=opens,
        highs=np.maximum(opens, closes) + 1e-4,
        lows=np.minimum(opens, closes) - 1e-4,
        extra={
            "event_no_trade_window_active": (rng.random(n) < 0.2).astype(float),
            "event_spread_stress_multiplier": 1 + rng.random(n),
            "event_slippage_stress_multiplier": 1 + rng.random(n),
        },
    ).reset_index()
    df.loc[[0, 3, n // 2, n - 1], "DATE_TIME"] = pd.NaT
    return df


def csv_feed(device=True):
    config = dict(DEFAULT_VALUES, window_size=WINDOW, timeframe="M1")
    md = MarketDataset(noisy_df(120), config).build_market_data(
        window_size=WINDOW, device=device)
    # a financing column that is not all zeros, through the one packer
    accrual = np.random.default_rng(1).normal(0, 1e-5, 120).astype(np.float32)
    xp = jnp if device else np
    return pack_bars(md._replace(rollover_accrual=xp.asarray(accrual)))


def scengen_host(n_bars=512):
    from gymfx_tpu.scengen.feed import ScenGenDataset

    cfg = dict(DEFAULT_VALUES)
    cfg.update(feed="scengen", scengen_preset="regime_mix", scengen_bars=n_bars,
               scengen_seed=3, scengen_snap_to_tick=True, window_size=WINDOW)
    return ScenGenDataset(cfg).build_market_data(window_size=WINDOW, device=False)


def producer_csv():
    md = csv_feed()
    assert int(np.asarray(md.minute_of_week).min()) == -1
    return md, 0, md.n_bars - 1


def producer_shard():
    host = csv_feed(device=False)
    shard = shard_market_data(host, 37, 50, WINDOW)
    assert int(shard.row0) == 37
    return jax.tree.map(jnp.asarray, shard), 37, 37 + 50


def producer_compressed():
    host = scengen_host()
    tape = C.encode_market_data(
        host, starts=[0, 200], shard_bars=300, window_size=WINDOW, tick_size=1e-5)
    shard = C.decode_shard_ref(tape, 1)
    assert int(shard.row0) == 200
    return jax.tree.map(jnp.asarray, shard), 200, 200 + 300


def producer_scengen():
    md = jax.tree.map(jnp.asarray, scengen_host())
    assert int(np.asarray(md.scen_flags).max()) > 0
    return md, 0, md.n_bars - 1


def producer_portfolio():
    from gymfx_tpu.core.portfolio import PortfolioEnvironment

    env = PortfolioEnvironment({
        "portfolio_files": {"EUR_USD": "examples/data/eurusd_sample.csv",
                            "USD_JPY": "examples/data/usdjpy_sample.csv"},
        "window_size": WINDOW, "initial_cash": 10000.0})
    pair = jax.tree.map(lambda leaf: leaf[1], env.data.pair)  # as vmap hands it
    return pair, 0, pair.n_bars - 1


PRODUCERS = {
    "csv_feed": producer_csv,
    "shard_row0_37": producer_shard,
    "compressed_decode_row0_200": producer_compressed,
    "scengen": producer_scengen,
    "portfolio_stack": producer_portfolio,
}

# the three index expressions of the step, from the cursor `t` before it
# and `n` bars (core/env.py _step, _event_overlay; core/obs.py)
EXPRESSIONS = {
    "new_bar": lambda t, n: jnp.where(t < n - 1, t + 1, t),
    "upcoming_bar": lambda t, n: jnp.minimum(jnp.minimum(t + 1, n), n - 1),
    "one_ahead_of_new_bar": lambda t, n: jnp.minimum(
        jnp.where(t < n - 1, t + 1, t) + 1, n - 1),
}


@pytest.fixture(scope="module")
def produced():
    cache = {}

    def get(name):
        if name not in cache:
            cache[name] = PRODUCERS[name]()
        return cache[name]

    return get


@pytest.mark.parametrize("expression", EXPRESSIONS)
@pytest.mark.parametrize("producer", PRODUCERS)
def test_packed_lookup_is_bitwise_the_per_column_reads(produced, producer, expression):
    data, first, last = produced(producer)
    t = jnp.arange(first, last + 1, dtype=jnp.int32)
    index = EXPRESSIONS[expression](t, last + 1)
    assert int(index.max()) == last and int(index.min()) >= first
    got = jax.jit(jax.vmap(lambda i: read_bar(data, i)))(index)
    want = jax.jit(jax.vmap(lambda i: column_reads(data, i)))(index)
    assert set(got) == set(BAR_COLUMNS)
    for field in BAR_COLUMNS:
        a, b = np.asarray(got[field]), np.asarray(want[field])
        assert a.dtype == b.dtype and a.shape == b.shape, field
        assert a.tobytes() == b.tobytes(), field


def test_one_table_of_24_words_in_the_documented_order():
    md = csv_feed()
    (table,) = md.bars
    assert table.shape == (md.n_bars, 24) and table.dtype == md.open.dtype
    words = np.asarray(table)
    for at, field in enumerate(BAR_COLUMNS[:10]):
        col = np.asarray(getattr(md, field))
        assert words[:, at].tobytes() == col.tobytes(), field
    assert words[:, 10:14].tobytes() == np.asarray(md.force_close).tobytes()
    assert words[:, 14:24].tobytes() == np.asarray(md.calendar).tobytes()


def test_a_wider_compute_dtype_rides_in_a_table_of_its_own():
    # the choice is made from the arrays' dtypes: 64-bit prices cannot
    # share a row with the 32-bit columns
    config = dict(DEFAULT_VALUES, window_size=WINDOW, timeframe="M1")
    md = MarketDataset(noisy_df(64), config).build_market_data(
        window_size=WINDOW, dtype=np.float64, device=False)
    prices, rest = md.bars
    assert prices.dtype == np.float64 and prices.shape == (64, 5)
    assert rest.dtype == np.int32 and rest.shape == (64, 19)
    assert rest[:, 0].tobytes() == md.minute_of_week.tobytes()
    assert rest[:, 2].view(np.float32).tobytes() == md.ev_no_trade.tobytes()


def test_a_tape_that_was_never_packed_is_refused_loudly():
    md = csv_feed()._replace(bars=())
    with pytest.raises(ValueError, match="pack_bars"):
        read_bar(md, jnp.int32(3))


# ---------------------------------------------------------------------------
# trajectory against the oracle
# ---------------------------------------------------------------------------
def trajectory(env, t0s, actions):
    """States, obs, rewards, dones and infos of len(actions) vmapped steps
    from resets at ``t0s`` (fresh traces: the reader is looked up anew)."""
    cfg, params, data = env.cfg, env.params, env.data
    vstep = jax.vmap(env_core.step, in_axes=(None, None, None, 0, 0))

    def run(t0s, actions):
        state, obs0 = jax.vmap(env_core.reset_at, in_axes=(None, None, None, 0))(
            cfg, params, data, t0s)

        def body(state, action):
            state, obs, reward, done, info = vstep(cfg, params, data, state, action)
            return state, (state, obs, reward, done, info)

        return obs0, jax.lax.scan(body, state, actions)

    return jax.jit(run)(t0s, actions)


@pytest.mark.parametrize("random_starts", [True, False])
def test_16_envs_8_steps_are_bitwise_the_column_reading_oracles(monkeypatch, random_starts):
    n = 40
    env = make_env(
        noisy_df(n, seed=7), window_size=WINDOW,
        event_context_execution_overlay=True, event_context_force_flat=True,
        stage_b_force_close_obs=True, stage_b_force_close_reward_penalty=True,
        oanda_fx_calendar_obs=True, enforce_margin_closeout=True)
    rng = np.random.default_rng(11)
    # some envs start on the last bars: they run into the n - 1 clamp
    t0s = (np.concatenate([rng.integers(0, n - 2, 12), [n - 2, n - 3, n - 5, 0]])
           if random_starts else np.zeros(16))
    t0s = jnp.asarray(t0s, jnp.int32)
    actions = jnp.asarray(rng.integers(0, 4, (8, 16)), jnp.int32)

    got = trajectory(env, t0s, actions)
    monkeypatch.setattr(env_core, "read_bar", column_reads)
    monkeypatch.setattr(obs_mod, "read_bar", column_reads)
    want = trajectory(env, t0s, actions)

    la, lb = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(la) == len(lb) > 100
    for a, b in zip(la, lb):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
    _obs0, (_state, (states, _obs, _reward, dones, _info)) = got
    assert int(states.trade_count.max()) > 0  # something happened
    if random_starts:
        assert bool(dones.any()) and not bool(dones.all())


# ---------------------------------------------------------------------------
# the mechanism's counter: gathers under rollout/env_step/tape_read
# ---------------------------------------------------------------------------
TAPE_READ = scopes.join(scopes.ROLLOUT, scopes.ENV_STEP, scopes.TAPE_READ)
ENV_STEP = scopes.join(scopes.ROLLOUT, scopes.ENV_STEP)
# `%gather.1 = f32[16,24]{1,0} gather(f32[300,24]{1,0} %table, ...` or, as
# a compiled executable prints it, `gather(%table, ...`
_GATHER = re.compile(r"\sgather\((?:\w+\[[\d,]*\]\S*\s+)?%?([\w.\-]+)")
_SHAPE = re.compile(r"\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\w+\[([\d,]*)\]")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_NAME = re.compile(r"\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def gathers_by_scope(hlo_text):
    """{scope path: [operand shape of every gather charged to it]}.  A
    gather is charged to the scopes on its own op path and, where it sits
    in a fusion, also to the scope ``scopes.scope_map_from_hlo`` gives the
    fusion (what a device trace charges its time to): under either it
    counts against the layer."""
    scope_map = scopes.scope_map_from_hlo(hlo_text)
    charged, computation = {}, None  # gather name -> (shape, {paths})
    in_computation = {}
    shape_of = {
        m.group(1): tuple(int(d) for d in m.group(2).split(",") if d)
        for m in map(_SHAPE.match, hlo_text.splitlines()) if m
    }
    for line in hlo_text.splitlines():
        if line[:1] not in (" ", "\t", ""):
            computation = line.split()[1 if line.startswith("ENTRY") else 0].lstrip("%")
            continue
        found = _GATHER.search(line)
        if not found:
            continue
        name = _NAME.match(line).group(1)
        shape = shape_of[found.group(1)]
        op_name = _OP_NAME.search(line)
        own = [part for part in re.findall(r"\w+", op_name.group(1) if op_name else "")
               if part in scopes.SCOPE_NAMES]
        paths = {scopes.join(*own)} if own else set()
        if name in scope_map:
            paths.add(scope_map[name].path)
        charged[name] = (shape, paths)
        in_computation.setdefault(computation, []).append(name)
    for line in hlo_text.splitlines():
        name = _NAME.match(line)
        calls = _CALLS.search(line) if " fusion(" in line else None
        if name and calls and name.group(1) in scope_map:
            for gather in in_computation.get(calls.group(1), []):
                charged[gather][1].add(scope_map[name.group(1)].path)
    out = {}
    for shape, paths in charged.values():
        for path in paths:
            out.setdefault(path, []).append(shape)
    return out


def compiled_step_text(**over):
    """The flagship's step (benchmarks/configs/ppo_mlp3x256_bf16.json) at
    the benchmark's rehearse sizes, compiled as the benchmark compiles it."""
    from gymfx_tpu.bench_util import compile_train_step
    from gymfx_tpu.core.runtime import Environment
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from

    config = dict(DEFAULT_VALUES)
    config.update(policy="mlp", policy_dtype="bfloat16", window_size=32,
                  ppo_minibatch_scheme="env_permute", ppo_epochs=1,
                  ppo_minibatches=4, rollout_collect_dtype="bfloat16",
                  rollout_env_kernel="interpret", num_envs=16, ppo_horizon=8,
                  timeframe="M1", **over)
    env = Environment(config, dataset=MarketDataset(noisy_df(300), config))
    trainer = PPOTrainer(env, ppo_config_from(config))
    step, _flops = compile_train_step(trainer, trainer.init_state(0))
    return step.as_text()


STEP_CONFIGS = {
    # name: (config over the flagship, distinct bar indices it reads)
    "flagship": ({}, 1),  # the new bar; found: 1 gather (the parent: 4)
    "flagship_random_starts": ({"random_episode_start": True}, 1),
    "every_column_read": (dict(
        event_context_execution_overlay=True, stage_b_force_close_obs=True,
        stage_b_force_close_reward_penalty=True, oanda_fx_calendar_obs=True), 3),
}


@pytest.mark.parametrize("name", STEP_CONFIGS)
def test_at_most_one_gather_per_distinct_bar_index_under_tape_read(name):
    over, distinct = STEP_CONFIGS[name]
    found = gathers_by_scope(compiled_step_text(**over))
    tape_reads = found.get(TAPE_READ, [])
    assert 1 <= len(tape_reads) <= distinct, (name, tape_reads)
    # every one reads the packed table: a row of 24 words a bar
    assert all(shape == (300, 24) for shape in tape_reads), tape_reads
    # and no column is gathered by bar index anywhere under the env step
    for path, shapes in found.items():
        if path == ENV_STEP or path.startswith(ENV_STEP + "/"):
            assert all(len(shape) > 1 for shape in shapes), (path, shapes)


def test_the_counter_counts_a_fusions_gathers_and_a_columns():
    hlo = """\
HloModule m

%fused.1 (p: f32[300], i: s32[16,1]) -> f32[16] {
  %p = f32[300]{0} parameter(0)
  %i = s32[16,1]{1,0} parameter(1)
  ROOT %gather.1 = f32[16]{0} gather(f32[300]{0} %p, s32[16,1]{1,0} %i), offset_dims={}, metadata={op_name="jit(step)/rollout/while/body/vmap(env_step)/tape_read/gather"}
}

ENTRY %main (a: f32[300], b: f32[300,24], i: s32[16,1]) -> f32[16] {
  %a = f32[300]{0} parameter(0)
  %b = f32[300,24]{1,0} parameter(1)
  %i = s32[16,1]{1,0} parameter(2)
  %gather.2 = f32[16,24]{1,0} gather(f32[300,24]{1,0} %b, s32[16,1]{1,0} %i), offset_dims={1}, metadata={op_name="jit(step)/rollout/while/body/vmap(env_step)/tape_read/gather"}
  ROOT %fusion.1 = f32[16]{0} fusion(f32[300]{0} %a, s32[16,1]{1,0} %i), kind=kLoop, calls=%fused.1, metadata={op_name="jit(step)/rollout/while/body/vmap(env_step)/tape_read/gather"}
}
"""
    assert gathers_by_scope(hlo) == {TAPE_READ: [(300,), (300, 24)]}
