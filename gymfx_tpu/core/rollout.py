"""Episode rollout: the driver loop as a single ``lax.scan``.

The reference runs a Python while-loop calling
``strategy.decide_action`` then ``env.step`` once per bar over two
thread context switches (reference app/main.py:58-66).  Here the whole
episode is one scanned XLA program; drivers are pure functions and the
rollout is jit/vmap-able (thousands of envs per device) — this is the
throughput path behind the 1M steps/sec target.

Built-in drivers mirror the reference driver modes
(reference strategy_plugins/default_strategy.py:44-54):
  buy_hold  long on the first step, hold after
  flat      always hold
  random    uniform over {0,1,2} per step
  replay    actions from an array, 0 past its end
plus ``policy`` (any callable obs->action) for trained agents.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from gymfx_tpu.core import env as env_core
from gymfx_tpu.core.types import EXEC_DIAG_INDEX, EnvConfig, EnvParams, EnvState
from gymfx_tpu.data.feed import MarketData


class Driver(NamedTuple):
    """A pure action source: carry -> (action, carry)."""

    init: Callable[[], Any]
    act: Callable[[Any, Dict[str, Any], Any, Any], Tuple[Any, Any]]
    # act(carry, obs, step_index, rng_key) -> (action, carry)


# Drivers are static jit arguments (compared by identity), so the
# built-ins are module-level singletons — a fresh Driver per call would
# re-trace and re-compile the whole episode scan on every rollout.
_BUY_HOLD = Driver(
    init=lambda: (),
    act=lambda carry, obs, i, key: (jnp.where(i == 0, 1, 0), carry),
)
_FLAT = Driver(
    init=lambda: (),
    act=lambda carry, obs, i, key: (jnp.zeros((), jnp.int32), carry),
)
_RANDOM = Driver(
    init=lambda: (),
    act=lambda carry, obs, i, key: (
        jax.random.randint(key, (), 0, 3, dtype=jnp.int32),
        carry,
    ),
)


def buy_hold_driver() -> Driver:
    return _BUY_HOLD


def flat_driver() -> Driver:
    return _FLAT


def random_driver() -> Driver:
    return _RANDOM


def replay_driver(actions) -> Driver:
    """Replay a host-provided action sequence; 0 past its end
    (reference default_strategy.py:50-53)."""
    arr = jnp.asarray(actions, dtype=jnp.int32)
    m = arr.shape[0]

    def act(carry, obs, i, key):
        a = jnp.where(i < m, arr[jnp.minimum(i, m - 1)], 0)
        return a, carry

    return Driver(init=lambda: (), act=act)


def policy_driver(apply_fn: Callable[..., Any], policy_params) -> Driver:
    """Wrap a policy network; apply_fn(policy_params, obs, rng) -> action."""

    def act(carry, obs, i, key):
        return apply_fn(policy_params, obs, key), carry

    return Driver(init=lambda: (), act=act)


DRIVERS = {
    "buy_hold": buy_hold_driver,
    "flat": flat_driver,
    "random": random_driver,
}


def _make_scan_body(cfg, params, data, driver, collect, offset,
                    collect_dtype=None):
    """The one scan body shared by rollout and rollout_chunked.

    ``collect_dtype`` (None = keep f32) narrows only the float
    *diagnostic* streams — reward and the pending/bracket price
    traces — to cut collected-buffer HBM traffic on long episodes.
    equity_delta/equity stay full precision (metrics derive equity
    from the delta in f64), and done/action/position/counters are
    integral and untouched.
    """
    _cd = (lambda x: x) if collect_dtype is None else (
        lambda x: x.astype(collect_dtype))

    def body(carry, i):
        state, obs, rng, dcarry = carry
        rng, key = jax.random.split(rng)
        action, dcarry = driver.act(dcarry, obs, offset + i, key)
        state, obs, reward, done, info = env_core.step(cfg, params, data, state, action)
        if collect:
            out = {
                # equity_delta carries the full precision: adding
                # initial_cash in f32 quantizes at ~1e-3 on a 10k account,
                # so metrics must derive equity from the delta in f64.
                "equity_delta": state.equity_delta,
                "equity": params.initial_cash + state.equity_delta,
                "reward": _cd(reward),
                "done": done,
                "action": jnp.asarray(action, dtype=jnp.int32),
                "position": jnp.sign(state.pos).astype(jnp.int32),
                "trade_count": state.trade_count,
                "bar_index": state.t + 1,
                # the pending order this step recorded (fills at the
                # NEXT bar's open) — the decision stream the replay
                # cross-check re-executes, incl. bracket prices
                # (simulation/crosscheck.py)
                "pending_active": state.pending_active,
                "pending_target": _cd(state.pending_target),
                "pending_sl": _cd(state.pending_sl),
                "pending_tp": _cd(state.pending_tp),
                "pos_units": state.pos,
                # the ACTUAL armed bracket levels and the venue-denial
                # counter after this step: the crosscheck builds each
                # bar's execution path from these instead of inferring
                # them from order history (stale levels / denied fills
                # would otherwise poison later bars' paths)
                "bracket_sl": _cd(state.bracket_sl),
                "bracket_tp": _cd(state.bracket_tp),
                "order_denied": state.exec_diag[
                    EXEC_DIAG_INDEX["order_denied_min_quantity"]
                ],
            }
            if cfg.event_context_execution_overlay:
                out["event_context"] = {
                    k: v for k, v in info.items()
                    if k.startswith("event_context_")
                }
        else:
            out = {}
        return (state, obs, rng, dcarry), out

    return body


@partial(jax.jit, static_argnames=("cfg", "steps", "driver", "collect",
                                   "collect_dtype"))
def rollout(
    cfg: EnvConfig,
    params: EnvParams,
    data: MarketData,
    driver: Driver,
    steps: int,
    rng: Any,
    collect: bool = True,
    driver_carry: Any = None,
    collect_dtype: Any = None,
):
    """Run one episode for ``steps`` env steps (frozen after termination).

    Returns (final_state, outputs) where outputs is a dict of per-step
    arrays (equity, reward, done, action, position) when ``collect``,
    else an empty dict — training collects its own trajectories.

    ``driver`` is a STATIC argument (jit cache key by identity); runtime
    data a driver needs (e.g. policy weights) must flow through
    ``driver_carry``, which is traced — that way re-evaluating with new
    weights reuses the compiled episode instead of retracing it.
    """
    state, obs = env_core.reset(cfg, params, data)
    init_carry = driver.init() if driver_carry is None else driver_carry
    body = _make_scan_body(cfg, params, data, driver, collect, 0,
                           collect_dtype)
    (state, obs, rng, _), outputs = jax.lax.scan(
        body, (state, obs, rng, init_carry), jnp.arange(steps)
    )
    return state, outputs


def episode_step_count(outputs) -> Any:
    """Steps executed before (and including) termination."""
    done = outputs["done"]
    return jnp.where(
        jnp.any(done), jnp.argmax(done) + 1, done.shape[-1]
    )


@partial(
    jax.jit,
    static_argnames=("cfg", "chunk", "driver", "collect", "collect_dtype"),
)
def _rollout_chunk(
    cfg, params, data, driver, chunk, state, obs, rng, dcarry, offset,
    collect=True, collect_dtype=None,
):
    """One fixed-size compiled segment of an episode (see rollout_chunked)."""
    body = _make_scan_body(cfg, params, data, driver, collect, offset,
                           collect_dtype)
    (state, obs, rng, dcarry), outputs = jax.lax.scan(
        body, (state, obs, rng, dcarry), jnp.arange(chunk)
    )
    return state, obs, rng, dcarry, outputs


def rollout_chunked(
    cfg: EnvConfig,
    params: EnvParams,
    data: MarketData,
    driver: Driver,
    steps: int,
    rng: Any,
    collect: bool = True,
    driver_carry: Any = None,
    chunk_size: int = 64,
    collect_dtype: Any = None,
):
    """Episode rollout as a host loop over fixed-size compiled segments.

    Behaviorally identical to ``rollout`` (same scan body), but the
    compiled program length is ``chunk_size`` regardless of ``steps`` —
    compile time of a long-episode scan grows with its length on some
    backends, and chunking also reuses one
    executable across every episode length.  At most two compiles per
    (cfg, driver): the chunk and the final remainder.
    """
    state, obs = env_core.reset(cfg, params, data)
    if steps <= 0:
        return state, {}
    dcarry = driver.init() if driver_carry is None else driver_carry
    pieces = []
    done_steps = 0
    while done_steps < steps:
        this = min(chunk_size, steps - done_steps)
        state, obs, rng, dcarry, out = _rollout_chunk(
            cfg, params, data, driver, this, state, obs, rng, dcarry,
            jnp.asarray(done_steps, jnp.int32), collect, collect_dtype,
        )
        if collect:
            pieces.append(out)
        done_steps += this
    if collect:
        outputs = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *pieces)
    else:
        outputs = {}
    return state, outputs


def rollout_streamed(
    cfg: EnvConfig,
    params: EnvParams,
    streamer,
    driver: Driver,
    steps: int,
    rng: Any,
    collect: bool = True,
    driver_carry: Any = None,
    chunk_size: int = 64,
    collect_dtype: Any = None,
):
    """Episode rollout over a :class:`~gymfx_tpu.data.feed.BarStreamer`.

    Behaviorally identical to ``rollout_chunked`` on the fully-resident
    dataset — same scan body, same cursor sequence; each shard's
    ``row0`` rebases the global bar cursor into shard-local array
    indices — but only two shards ever occupy device memory, and the
    streamer enqueues shard ``t+1``'s host→device transfer before the
    chunks of shard ``t`` are dispatched, so the DMA overlaps compute.

    Every shard has identical static shapes, so all shards share the
    same compiled chunk executable(s).

    Caveat: an episode that terminates mid-stream freezes its cursor at
    the terminal bar; once serving moves to a shard that no longer
    covers the frozen cursor, the (inert, post-``done``) obs/info reads
    clamp to the shard edge and may differ from the resident path.
    Steps at or before termination are bit-identical.
    """
    state = obs = None
    dcarry = driver.init() if driver_carry is None else driver_carry
    pieces = []
    done_steps = 0
    for lo, hi, shard in streamer.iter_shards():
        if state is None:
            # cursor starts at bar 0 — shard 0 always covers it
            state, obs = env_core.reset(cfg, params, shard)
            if steps <= 0:
                return state, {}
        # step i advances the cursor to bar i (i=0 is the warmup step at
        # bar 0): shard serving cursors [lo, hi) runs steps [lo, hi)
        end = steps if hi is None else min(int(hi), steps)
        while done_steps < end:
            this = min(chunk_size, end - done_steps)
            state, obs, rng, dcarry, out = _rollout_chunk(
                cfg, params, shard, driver, this, state, obs, rng, dcarry,
                jnp.asarray(done_steps, jnp.int32), collect, collect_dtype,
            )
            if collect:
                pieces.append(out)
            done_steps += this
        if done_steps >= steps:
            break
    if collect and pieces:
        outputs = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *pieces)
    else:
        outputs = {}
    return state, outputs
