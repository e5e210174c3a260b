"""The train runner: drives the trainer's own step program for a window.

The program measured is the one ``PPOTrainer.train`` dispatches at the
default ``supersteps_per_dispatch 1``: ``trainer._train_step``, compiled
once by ``bench_util.compile_train_step`` (its text also gives the count
of ``tpu_custom_call``).  Set-up: tape, environment, the configuration's
correctness check (``checks/<kind>.py``), compile or cache load, warm-up.
Window: dispatch until the clock runs out, always waiting for the metrics
of the step before the last one (the device keeps one program queued, the
host cannot run ahead, and every finished step's loss is fetched as a
training loop's logging would), closed by ``block_until_ready``.

Traced run (``--trace 1``): no timed window; the two phase programs the
step is composed of, each synchronised (a copy of
``bench_util.measure_phase_split``), then a profiled window of
``trace_steps`` train steps.
"""
from __future__ import annotations

import gc
import math
import statistics
import time
from collections import deque

import harness
from tape import ensure_tape


def kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def host_copy(tree):
    import jax
    import numpy as np

    return jax.tree.map(np.asarray, jax.device_get(tree))


def phase_split(trainer, state, iters: int):
    """Rollout and update as two donated programs off the phase methods the
    fused step is composed of; every call synchronised.  The sum overstates
    the fused step (two dispatches, a host sync, no cross-phase fusion), so
    each is reported beside the fused step time.  Returns (per-iteration
    rollout seconds, update seconds, final state)."""
    import jax

    r_step = jax.jit(trainer._rollout_phase, donate_argnums=0).lower(state).compile()
    inter, out = r_step(state)
    u_step = jax.jit(trainer._update_phase, donate_argnums=(0, 1)).lower(
        inter, out).compile()
    state, _ = u_step(inter, out)
    jax.block_until_ready(state)
    rollout, update = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.rollout_phase"):
            inter, out = r_step(state)
            jax.block_until_ready((inter, out))
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.update_phase"):
            state, _ = u_step(inter, out)
            jax.block_until_ready(state)
        update.append(time.perf_counter() - t1)
        rollout.append(t1 - t0)
    return rollout, update, state


def drive(step, state, more):
    """Dispatch train steps while ``more(steps so far)``, one program
    queued behind the one that runs: before each new dispatch but the
    first, wait for the loss of the step before the last one.  Closed by
    ``block_until_ready``.  Returns (state, steps dispatched, every loss)."""
    import jax

    pending, losses, n = deque(), [], 0
    while more(n):
        with jax.profiler.TraceAnnotation("bench.train_step"):
            state, m = step(state)
        n += 1
        pending.append(m["loss"])
        if len(pending) > 1:
            with jax.profiler.TraceAnnotation("bench.fetch_loss"):
                losses.append(float(pending.popleft()))
    with jax.profiler.TraceAnnotation("bench.block_until_ready"):
        jax.block_until_ready(state)
    losses.extend(float(x) for x in pending)
    return state, n, losses


def run(ctx: harness.Context) -> dict:
    import jax

    from gymfx_tpu.bench_util import compile_train_step
    from gymfx_tpu.core.runtime import Environment
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from

    conf, traffic = ctx.cell["config"], ctx.cell["traffic"]
    compiles = harness.CompileCounter()
    seed = harness.seed31(ctx.seed)
    t = time.perf_counter()
    tape = ensure_tape(traffic["tape"])
    config = harness.program_config(ctx.cell, input_data_file=str(tape))
    mesh = None
    if traffic.get("mesh"):
        from gymfx_tpu.parallel import make_mesh

        mesh = make_mesh(traffic["mesh"], devices=ctx.devices)
    env = Environment(config)
    tape_s = time.perf_counter() - t

    def build(**over):
        """A trainer of this cell with ``over`` laid on its program config,
        on the same loaded tape (what a check's plain twin is built with)."""
        cfg = {**config, **over}
        return PPOTrainer(Environment(cfg, dataset=env.dataset),
                          ppo_config_from(cfg), mesh=mesh)

    def first_steps(trainer):
        """Compile the trainer's step program and run its warm-up steps from
        ``--seed``: (state, compiled step, what a check compares: per-step
        metrics, env state and params on the host, the kernel count)."""
        state = trainer.init_state(seed)
        step, _flops = compile_train_step(trainer, state)
        metrics = []
        for _ in range(warmup):
            state, m = step(state)
            metrics.append(host_copy(m))
        return state, step, {
            "metrics": metrics, "env_states": host_copy(state.env_states),
            "params": host_copy(state.params), "tpu_custom_calls": kernel_calls(step)}

    # ---- set-up: the check's reference first (freed before the measured
    # program holds the chip's memory), then the measured program
    spec = conf["check"]
    checker = harness.load_module("checks", spec["kind"])
    warmup = int(traffic.get("warmup_steps", 2))
    t = time.perf_counter()
    reference = checker.reference(ctx, spec, build, first_steps)
    gc.collect()
    check_s = time.perf_counter() - t

    t = time.perf_counter()
    trainer = PPOTrainer(env, ppo_config_from(config), mesh=mesh)
    state, step, first = first_steps(trainer)
    compile_s = time.perf_counter() - t
    calls = first["tpu_custom_calls"]
    check_ok, check_detail = checker.verdict(spec, reference, first)
    del reference
    steps_per_step = int(config["num_envs"]) * int(config["ppo_horizon"])
    harness.note(setup={"tape_s": tape_s, "check_s": check_s,
                        "compile_and_warmup_s": compile_s,
                        "cache_hits": compiles.hits, "cache_misses": compiles.misses},
                 step_temporaries_bytes=int(step.memory_analysis().temp_size_in_bytes),
                 tpu_custom_calls=calls, kernels_expected=conf["kernels_expected"],
                 env_steps_per_train_step=steps_per_step,
                 n_bars=int(trainer.env.cfg.n_bars),
                 params=sum(x.size for x in jax.tree.leaves(state.params)),
                 check=check_detail,
                 warmup_loss=[float(m["loss"]) for m in first["metrics"]])

    end_to_end, trace, spans = {}, {}, {}
    compiled_before = compiles.count
    if ctx.trace:
        rollout, update, state = phase_split(
            trainer, state, int(traffic.get("phase_iters", 5)))
        compiled_before = compiles.count
        spans = {"rollout_s": rollout, "update_s": update}
        budget = int(traffic.get("trace_steps", 8))
        jax.block_until_ready(state)
        with harness.traced_window(ctx.cell["name"], trace):
            state, dispatched, losses = drive(step, state, lambda n: n < budget)
        window_s = trace["window_s"]
    else:
        window_start = time.perf_counter()
        end_to_end["setup_s"] = window_start - ctx.t0
        state, dispatched, losses = drive(
            step, state, lambda n: time.perf_counter() - window_start < ctx.seconds)
        window_s = time.perf_counter() - window_start
        end_to_end["env_steps_per_s"] = dispatched * steps_per_step / window_s
    compiled_inside = compiles.count - compiled_before

    failed = sum(not math.isfinite(x) for x in losses)
    correct = (check_ok and failed == 0 and compiled_inside == 0
               and calls >= int(conf["kernels_expected"]) and dispatched > 0)
    harness.note(window_s=window_s, train_steps=dispatched,
                 step_ms=1e3 * window_s / max(dispatched, 1),
                 compiled_inside_window=compiled_inside, check_ok=check_ok,
                 loss_first_last=[losses[0], losses[-1]] if losses else [],
                 phase_ms={k: 1e3 * statistics.median(v) for k, v in spans.items()})
    return {"correct": correct, "attempted": dispatched, "failed": failed,
            "end_to_end": end_to_end, "trace": trace, "spans": spans,
            "counters": {"tpu_custom_calls": calls, "train_steps": dispatched,
                         "env_steps_per_train_step": steps_per_step},
            "cell": ctx.cell}
