"""The serve runner of a STATELESS window policy: every request carries its
whole window, and the engine holds no session state.

Set-up: the tape and the environment; the weights, made by the benchmark from
``--seed`` in one jitted call (``decoder_weights.py``: one chip's share of the
trunk in the type it is served in, the choice bias balanced on a batch of
windows drawn as the traffic draws them); ``engine_from_config(config,
params=...)``, whose warm boot compiles or loads every bucket of the ladder and
runs each once; ``batcher_from_config``; the mix's schedule and every request's
window, encoded on the device by the program's own reset and encoder before the
clock starts.  No slots to seed.

Traffic (``traffic/<mix>.json``): an open loop of Poisson arrivals at ``rate``
(``loadgen.make_schedule`` drawn from the file's ``arrivals_seed``: one pattern
of arrivals, the same in every run, so that a tail measures the system and not
how bursty a seed's draw was) over ``sessions`` sessions drawn from ``--seed``;
each session starts at a uniform offset on the tape, its window full of bars,
and advances one bar per request it sends.  Window: ``loadgen.send`` offers the
schedule from THIS thread after a lead-in of the same traffic (not judged);
latencies run from due times; what is still out when the schedule ends gets
``drain_s`` to be answered.

After the window (outside ``setup_s`` and the window): the peak memory is read
and handed on as the run's (the reference's own may not count), a sample of
the requests answered in the window is drawn from ``--seed``, the serving stack
is freed, and ``checks/<check.kind>.py`` holds what the engine served them
against the plain reference, which builds its own tokens from the tape and
the bar each window ends at.  The open loop, its summary and the terms of
``correct`` all serving cells share are ``serving.py``'s.

``sweep_knee.py`` steps through rates with ``Stack.offer``: each step draws
its own arrival times from its seed, so that no step replays the cell's one
pattern and its last burst.

Traced run: the same open loop for the traffic file's ``trace_seconds`` under the
profiler; the counter ``decisions_traced`` (every request answered inside the
profiled window, lead-in and drain included) is what ``serve_policy_mfu`` counts.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass

import numpy as np

import decoder_weights
import harness
import loadgen
import serving
from runners.train import kernel_calls
from tape import ensure_tape


@dataclass
class Offer:
    schedule: loadgen.Schedule
    bars: np.ndarray        # the bar each request's window ends at
    windows: np.ndarray     # (requests, window, token_dim), as the engine encodes them


class Stack:
    """The booted serving stack and the tape's windows, by bar."""

    def __init__(self, ctx: harness.Context):
        import jax
        import jax.numpy as jnp

        from gymfx_tpu.core import env as env_core
        from gymfx_tpu.core.runtime import Environment
        from gymfx_tpu.serve import batcher_from_config, engine_from_config
        from gymfx_tpu.train.policies import (
            make_obs_encoder,
            make_obs_spec,
            make_trainer_policy,
            policy_kwargs_from,
        )

        conf, traffic = ctx.cell["config"], ctx.cell["traffic"]
        t = time.perf_counter()
        self.tape_path = ensure_tape(traffic["tape"])
        config = harness.program_config(
            ctx.cell, input_data_file=str(self.tape_path), seed=harness.seed31(ctx.seed))
        self.feature_columns = list(config["feature_columns"])
        env = Environment(config)
        cfg, eparams, data = env.cfg, env.params, env.data
        _state, reset_obs = env_core.reset(cfg, eparams, data)
        encode = make_obs_encoder(config["policy"], cfg.window_size, make_obs_spec(reset_obs))
        self.window, self.n_bars = int(cfg.window_size), int(cfg.n_bars)
        self._encoded = jax.jit(jax.vmap(
            lambda p, d, t0: encode(env_core.reset_at(cfg, p, d, t0)[1]),
            in_axes=(None, None, 0)))
        self._env_args = (eparams, data)
        self.tape_s = time.perf_counter() - t

        t = time.perf_counter()
        weights = conf["weights"]
        dtype = jnp.dtype(weights["dtype"])
        kwargs = policy_kwargs_from(config)
        policy = make_trainer_policy(
            config["policy"], continuous=False,
            dtype={"float32": jnp.float32, "bfloat16": jnp.bfloat16}[config["policy_dtype"]],
            kwargs=kwargs, window=self.window)
        rng = np.random.default_rng(harness.seed31(ctx.seed, 6))
        init_tokens = self.windows_at(self.session_starts(rng, int(weights["init_windows"])))
        abstract = jax.eval_shape(policy.init, jax.random.PRNGKey(0), init_tokens[:1])
        self.cfg = decoder_weights.reference_cfg(kwargs)
        self.params = decoder_weights.make(abstract, init_tokens, ctx.seed, self.cfg, dtype)
        jax.block_until_ready(self.params)
        self.weights_s = time.perf_counter() - t

        t = time.perf_counter()
        self.bundle = engine_from_config(config, params=self.params, env=env)
        self.engine = engine = self.bundle.engine
        self.batcher = batcher_from_config(engine, config)
        self.boot_s = time.perf_counter() - t
        self.sessions = int(traffic["sessions"])
        self.arrivals_seed = int(traffic["arrivals_seed"])
        # the set-up's objects (traces of the weights' program and of the ladder's)
        # out of the collector's reach: a full collection while requests are sent
        # scans only what serving allocates (without it the load generator ran
        # 14-72 ms late in about one run in four)
        gc.collect()
        gc.freeze()

    def session_starts(self, rng, n: int, advance: int = 0) -> np.ndarray:
        """``n`` bars drawn uniformly where a window ending there is full of
        bars and ``advance`` bars more stay on the tape."""
        return rng.integers(self.window - 1, self.n_bars - 1 - advance, n)

    def windows_at(self, bars) -> np.ndarray:
        """The encoded windows ending at ``bars`` (host float32).  The batch is
        padded to a multiple of 512 rows, so few shapes compile."""
        bars = np.asarray(bars, np.int32)
        padded = np.resize(bars, -(-len(bars) // 512) * 512)
        return np.asarray(self._encoded(*self._env_args, padded))[:len(bars)]

    def prepare(self, seed: int, rate: float, lead_in: float, span_s: float,
                arrivals_seed: int | None = None) -> Offer:
        """The schedule of a lead-in and a window at ``rate`` and every
        request's window.  The arrival times are ``arrivals_seed``'s, by
        default the traffic file's, the same for every run; ``seed`` draws
        which session sends each request and where each session starts
        (session s's k-th request ends at its start + k): the same arrivals
        carrying other windows, in another order."""
        schedule = loadgen.make_schedule(
            self.arrivals_seed if arrivals_seed is None else arrivals_seed,
            rate, (lead_in, span_s), self.sessions, 1)
        rng = np.random.default_rng(harness.seed31(seed, 1))
        schedule.session = rng.integers(0, self.sessions, len(schedule.session))
        step = np.zeros(len(schedule.session), np.int64)
        sent = np.zeros(self.sessions, np.int64)
        for i, s in enumerate(schedule.session):
            step[i], sent[s] = sent[s], sent[s] + 1
        bars = self.session_starts(rng, self.sessions, int(sent.max()))[schedule.session] + step
        return Offer(schedule, bars, self.windows_at(bars))

    def send(self, offer: Offer, lead_in: float, span_s: float, drain_s: float, span=None):
        """Offer the schedule open loop; wait up to ``drain_s`` for what is
        still out.  Returns (sent, futures, window start, window end, end of
        the wait)."""
        batcher, windows = self.batcher, offer.windows
        futures = [None] * len(windows)

        def submit(i):
            futures[i] = batcher.submit(windows[i])
            return futures[i]

        start = time.perf_counter()
        sent = loadgen.send(offer.schedule, submit, start=start, span=span)
        loadgen.wait_answers(sent, drain_s)
        return sent, futures, start + lead_in, start + lead_in + span_s, time.perf_counter()

    def seed_slots(self) -> float:
        """A stateless engine holds no session: nothing to seed."""
        return 0.0

    def offer(self, seed: int, rate: float, lead_in: float, span_s: float,
              answer_s: float, span=None):
        """``sweep_knee.py``'s step: (sent, window start, window end, end of
        the wait).  Its arrival times are drawn from ``seed`` too."""
        sent, _futures, t0, t1, t_end = self.send(
            self.prepare(seed, rate, lead_in, span_s, arrivals_seed=seed),
            lead_in, span_s, answer_s, span)
        return sent, t0, t1, t_end

    def close(self):
        """Stop the batcher and drop the engine (its executables and buffers);
        the weights stay for the check."""
        self.batcher.close(timeout=30)
        del self.batcher, self.engine, self.bundle
        gc.collect()


def sample(seed: int, sent, t0: float, t1: float, decisions: int) -> np.ndarray:
    """Indices of the requests the check compares: ``decisions`` drawn from
    ``seed`` among those due in the window and answered (every one where the
    window answered fewer)."""
    inside = np.flatnonzero((sent.due >= t0) & (sent.due < t1) & ~np.isnan(sent.done))
    if len(inside) <= decisions:
        return inside
    return np.sort(np.random.default_rng(seed).choice(inside, decisions, replace=False))


def run(ctx: harness.Context) -> dict:
    import jax

    conf, traffic = ctx.cell["config"], ctx.cell["traffic"]
    spec = conf["check"]
    compiles = harness.CompileCounter()
    stack = Stack(ctx)
    engine, batcher = stack.engine, stack.batcher
    calls = sum(kernel_calls(exe) for exe in engine._compiled.values())
    rate, lead_in = float(traffic["rate"]), float(traffic["lead_in_s"])
    span_s = float(traffic["trace_seconds"]) if ctx.trace else ctx.seconds
    t = time.perf_counter()
    offer = stack.prepare(harness.seed31(ctx.seed, 4), rate, lead_in, span_s)
    harness.note(setup={"tape_s": stack.tape_s, "weights_s": stack.weights_s,
                        "boot_s": stack.boot_s, "windows_s": time.perf_counter() - t,
                        "cache_hits": compiles.hits, "cache_misses": compiles.misses},
                 params=sum(x.size for x in jax.tree.leaves(stack.params)),
                 params_bytes=sum(x.nbytes for x in jax.tree.leaves(stack.params)),
                 buckets=list(engine.buckets), rate=rate, requests=len(offer.bars),
                 tpu_custom_calls=calls, kernels_expected=conf["kernels_expected"])
    futures = []

    def send(span):
        sent, served, t0, t1, t_end = stack.send(
            offer, lead_in, span_s, float(traffic["drain_s"]), span)
        futures.extend(served)
        return sent, t0, t1, t_end

    win = serving.open_loop(ctx, compiles, lead_in, send)
    sent, t0, t1 = win.sent, win.t0, win.t1
    every_record = batcher.records
    records = [r for r in every_record if t0 <= r.t_enqueue < t1]
    answered = ~np.isnan(sent.done)
    counters = {**serving.counters(engine, batcher, win),
                "unanswered": int((~answered).sum()), "decisions_traced": int(answered.sum()),
                "tpu_custom_calls": calls}
    peak = harness.device_report(ctx.devices, 1)["memory_peak_bytes"]

    # ---- the check, once the window has closed and the serving stack is freed
    picked = sample(harness.seed31(ctx.seed, 7), sent, t0, t1, int(spec["decisions"]))
    decisions = [futures[i].result() for i in picked]
    rows = serving.bucket_rows(sent, every_record)
    served = {"logits": np.stack([np.asarray(d.actor_out, np.float32) for d in decisions]),
              "value": np.asarray([float(d.value) for d in decisions], np.float32),
              "action": np.asarray([int(d.action) for d in decisions]),
              "windows": offer.windows[picked], "bars": offer.bars[picked],
              "batch": None if rows is None else rows[0][picked].tolist(),
              "row": None if rows is None else rows[1][picked].tolist()}
    stack.close()
    del engine, batcher, futures, decisions
    t = time.perf_counter()
    checker = harness.load_module("checks", spec["kind"])
    tape = {"values": harness.load_module("checks", "window_tokens").load_columns(
        stack.tape_path, stack.feature_columns), "window": stack.window}
    check_ok, check_detail = checker.check(spec, stack.params, stack.cfg, tape, served)
    check_detail["sample"] = [[int(offer.schedule.session[i]), int(offer.bars[i])]
                              for i in picked]
    correct = (check_ok and serving.served_soundly(counters, conf, win)
               and counters["unanswered"] == 0 and counters["errors"] == 0
               and calls >= int(conf["kernels_expected"]) and len(picked) > 0)
    harness.note(window=win.summary, dispatch=serving.dispatch_stats(records, t0),
                 counters=counters, check_s=time.perf_counter() - t, check=check_detail,
                 check_ok=check_ok)
    return serving.result(
        correct, win, counters, records, ctx.cell, memory_peak_bytes=peak,
        compared={name: {"value": check_detail.get(name), "limit": limit}
                  for name, limit in spec["limits"].items()})
