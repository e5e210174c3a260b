#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that gymfx-tpu still starts on the chip.

    python chip_smoke.py            # one TPU chip: every phase below
    python chip_smoke.py --chips 4  # four chips: ONLY the sharded phase

ONE PROCESS.  Every phase runs in this process, one after the other; the
script starts no child (a chip belongs to one process: a parent that has
touched JAX holds it, and a child that needs it then fails or hangs).

It drives the system through the entry points a user would call — the
CLI's ``gymfx_tpu.app.main.main`` (``python -m gymfx_tpu.app.main``),
``Environment`` + ``PPOTrainer``, ``gymfx_tpu.serve.engine_from_config``
-> ``MicroBatcher`` — at the widths the repo publishes (3x256 MLP,
256-wide LSTM, 2-layer d_model=128 transformer), weights random from a
seed, and checks what comes out against the repo's own references (the
plain-XLA twin of each Pallas kernel, the argsort LOB engine, the jitted
unbatched policy).  Every program meant to hold a kernel must show a
``tpu_custom_call`` in its compiled text.

Output: one JSON object per phase on its own line, then, as the LAST
line, ``{"ok": ..., "device": {"platform", "kind", "count"}}``.  Exit
code 0 only if every phase passed.  No accelerator -> non-zero exit at
the ``device`` phase, before any work; there is no CPU mode.

The times printed are SMOKE TIMINGS on the stated device (a few steps,
compile reported apart), not benchmark metrics.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parent
SAMPLE_CSV = str(REPO / "examples" / "data" / "eurusd_sample.csv")
OUT_DIR = REPO / "chiprun_out" / "chip_smoke"

# repo-published sizes (bench.py flagship, tools/tpu_bench.py sweep rows,
# ROADMAP "Reach"); none is shrunk for the chip
SIZES = {
    "mlp_envs": 8192, "horizon": 64, "window": 32,
    "tr_envs": 256, "tr_window": 256,
    "lstm_envs": 4096,
    # the LOB venue's intrabar flow scan (64 messages a bar through the
    # argsort engine) costs ~2 s per rollout step at 1024 envs on the chip
    # (PR 22: 127.7 s per 64-step train step), so this phase keeps the
    # published book and batch and takes a 16-step horizon
    "lob_envs": 1024, "lob_depth": 24, "lob_slots": 4, "lob_horizon": 16,
    "decode_bars": 229376,
    "serve_sessions": 64, "serve_decisions": 512,
    "cli_steps": 400, "cli_envs": 256,
    "sharded_envs": 8192,
}


def emit(obj) -> None:
    print(json.dumps(obj, default=str), flush=True)


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------
def flagship_config(**over):
    """bench.py's flagship: PPO, 3x256 MLP in bf16, env-permuted
    minibatches, window 32, bf16 collect."""
    from gymfx_tpu.config import DEFAULT_VALUES

    config = dict(DEFAULT_VALUES)
    config.update(
        input_data_file=SAMPLE_CSV, num_envs=SIZES["mlp_envs"],
        ppo_horizon=SIZES["horizon"], ppo_epochs=1, ppo_minibatches=4,
        policy="mlp", policy_dtype="bfloat16",
        ppo_minibatch_scheme="env_permute", window_size=SIZES["window"],
        rollout_collect_dtype="bfloat16",
    )
    config.update(over)
    return config


def make_trainer(config, mesh=None):
    from gymfx_tpu.core.runtime import Environment
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from

    return PPOTrainer(Environment(config), ppo_config_from(config), mesh=mesh)


def kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def run_steps(trainer, steps: int, *, seed: int = 0):
    """Compile the trainer's donated step program once and run ``steps``
    train steps (the first is the warm-up).  Returns (state, per-step
    metrics, info, the compiled step)."""
    import jax
    import numpy as np

    from gymfx_tpu.bench_util import compile_train_step

    state = trainer.init_state(seed)
    t0 = time.perf_counter()
    step, _flops = compile_train_step(trainer, state)
    compile_s = time.perf_counter() - t0
    calls = kernel_calls(step)
    metrics, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, m = step(state)
        jax.block_until_ready(state)
        times.append(time.perf_counter() - t0)
        metrics.append({k: np.asarray(v) for k, v in jax.device_get(m).items()})
    info = {
        "compile_s": round(compile_s, 3),
        "first_step_s": round(times[0], 4),
        "steady_step_s": round(float(np.median(times[1:])), 5)
        if len(times) > 1 else None,
        "tpu_custom_calls": calls,
        "loss": [float(m["loss"]) for m in metrics],
    }
    return state, metrics, info, step


def run_superstep(trainer, state, k: int):
    """One K-step ``train_many`` superstep (a single donated dispatch)
    from ``state``; returns its info."""
    import jax
    import numpy as np

    from gymfx_tpu.bench_util import compile_train_step

    t0 = time.perf_counter()
    many, _flops = compile_train_step(trainer, state, k)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    state, stacked = many(state)
    jax.block_until_ready(state)
    run_s = time.perf_counter() - t0
    loss = np.asarray(jax.device_get(stacked)["loss"], np.float64)
    return {
        "superstep_k": k, "superstep_compile_s": round(compile_s, 3),
        "superstep_s": round(run_s, 4),
        "superstep_tpu_custom_calls": kernel_calls(many),
        "superstep_loss": [float(x) for x in loss],
        "superstep_ok": loss.shape == (k,) and bool(np.isfinite(loss).all())
        and all_finite(state.params),
    }


def all_finite(tree) -> bool:
    import jax
    import jax.numpy as jnp

    return all(
        bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(tree)
        if jnp.issubdtype(x.dtype, jnp.floating)
    )


def tree_diff(a, b):
    """(bitwise_equal, max_abs_diff over float leaves, n_leaves_differing)"""
    import jax
    import numpy as np

    la = jax.tree.leaves(jax.device_get(a))
    lb = jax.tree.leaves(jax.device_get(b))
    assert len(la) == len(lb)
    worst, differing = 0.0, 0
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            return False, float("inf"), len(la)
        if not np.array_equal(x, y, equal_nan=x.dtype.kind == "f"):
            differing += 1
            d = np.abs(x.astype(np.float64) - y.astype(np.float64))
            worst = max(worst, float(np.nanmax(d)))
    return differing == 0, worst, differing


def compare_runs(on, off, *, loss_rtol: float):
    """First-step loss and the whole trajectory (per-step metrics and the
    final env batch) of a kernel run against its plain-XLA oracle run."""
    state_on, metrics_on = on[:2]
    state_off, metrics_off = off[:2]
    loss_on = [float(m["loss"]) for m in metrics_on]
    loss_off = [float(m["loss"]) for m in metrics_off]
    first = abs(loss_on[0] - loss_off[0])
    bit_metrics, worst_metric, _ = tree_diff(metrics_on, metrics_off)
    bit_env, worst_env, env_leaves = tree_diff(
        state_on.env_states, state_off.env_states
    )
    bit_params, worst_params, _ = tree_diff(state_on.params, state_off.params)
    ok = first <= loss_rtol * max(1.0, abs(loss_off[0]))
    return ok, {
        "oracle_loss": loss_off,
        "first_step_loss_abs_diff": first,
        "metrics_bitwise": bit_metrics, "metrics_max_abs_diff": worst_metric,
        "env_states_bitwise": bit_env, "env_states_max_abs_diff": worst_env,
        "env_state_leaves_differing": env_leaves,
        "params_bitwise": bit_params, "params_max_abs_diff": worst_params,
    }


# ---------------------------------------------------------------------------
# phases (one chip)
# ---------------------------------------------------------------------------
def phase_train_mlp():
    """The flagship of bench.py: both env-dynamics kernels in the
    program, against the same seed with the kernel off."""
    trainer = make_trainer(flagship_config(rollout_env_kernel="on"))
    on = run_steps(trainer, 5)
    off = run_steps(make_trainer(flagship_config(rollout_env_kernel="off")), 5)
    ok, cmp = compare_runs(on, off, loss_rtol=1e-3)
    ok = ok and all_finite(on[0].params)
    info = {**on[2], **run_superstep(trainer, on[0], 2)}
    ok = (
        ok and info["tpu_custom_calls"] >= 2 and info["superstep_ok"]
        and info["superstep_tpu_custom_calls"] >= 2
        and off[2]["tpu_custom_calls"] == 0
    )
    from gymfx_tpu.data import native_loader

    return {
        "ok": ok, "envs": SIZES["mlp_envs"], "horizon": SIZES["horizon"],
        "window": SIZES["window"], "policy": "mlp 3x256 bf16",
        "csv_loader": native_loader.served_by(SAMPLE_CSV),
        **info, "oracle_compile_s": off[2]["compile_s"],
        "oracle_steady_step_s": off[2]["steady_step_s"], **cmp,
    }


def phase_train_mlp_features():
    """The same trainer with feature columns, so that n_features > 0 and
    rollout_obs_kernel=on really puts fused_step_obs in the program."""
    from gymfx_tpu.core.types import make_env_config

    features = dict(
        feature_columns=["OPEN", "HIGH", "LOW", "CLOSE", "VOLUME"],
        feature_scaling="rolling_zscore", feature_scaling_window=64,
    )
    on = run_steps(make_trainer(flagship_config(
        rollout_env_kernel="on", rollout_obs_kernel="on", **features)), 2)
    env_only = run_steps(make_trainer(flagship_config(
        rollout_env_kernel="on", rollout_obs_kernel="off", **features)), 2)
    ok, cmp = compare_runs(on, env_only, loss_rtol=1e-3)
    obs_bitwise, obs_diff, _ = tree_diff(on[0].obs_vec, env_only[0].obs_vec)
    # a switch that cannot apply is refused, not accepted and ignored
    refused = False
    try:
        make_env_config(
            flagship_config(rollout_obs_kernel="on"), n_bars=500, n_features=0
        )
    except ValueError as exc:
        refused = "rollout_obs_kernel" in str(exc)
    obs_calls = on[2]["tpu_custom_calls"] - env_only[2]["tpu_custom_calls"]
    ok = ok and obs_calls >= 1 and refused
    return {
        "ok": ok, "n_features": 5, **on[2],
        "oracle_steady_step_s": env_only[2]["steady_step_s"],
        "obs_kernel_custom_calls": obs_calls,
        "obs_vec_bitwise_vs_scale_feature_window": obs_bitwise,
        "obs_vec_max_abs_diff": obs_diff,
        "obs_kernel_refused_without_features": refused, **cmp,
    }


def _attention_parity(dtype_name: str):
    """fused_window_attention (compiled) vs the full_attention twin at
    the policy's shape: output, and q/k/v gradients of sum(out ** 2) —
    the comparison of tests/test_ops.py's TPU-marked test."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gymfx_tpu.ops.fused_attention import fused_window_attention
    from gymfx_tpu.parallel.ring_attention import full_attention

    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype_name]
    shape = (SIZES["tr_envs"], SIZES["tr_window"], 4, 32)
    q, k, v = (
        jax.random.normal(key, shape, jnp.float32).astype(dtype)
        for key in jax.random.split(jax.random.PRNGKey(5), 3)
    )

    def fused_fn(q, k, v):
        return fused_window_attention(q, k, v, interpret=False)

    def grad_of(fn):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2),
        ))

    fused = jax.jit(fused_fn).lower(q, k, v).compile()
    fused_grad = grad_of(fused_fn).lower(q, k, v).compile()
    assert kernel_calls(fused) >= 1 and kernel_calls(fused_grad) >= 1
    got, got_grad = fused(q, k, v), fused_grad(q, k, v)

    def worst(a, b):
        return float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32))))

    out = {"finite": bool(np.isfinite(np.asarray(got, np.float32)).all())}
    # the float32 kernel asks the MXU for true f32 (Precision.HIGHEST);
    # the twin is asked for the same, and, for the record, also run at
    # XLA:TPU's default precision (one bf16 pass)
    for tag in ("highest", "default"):
        with jax.default_matmul_precision(tag):
            ref = jax.jit(full_attention)(q, k, v)
            ref_grad = grad_of(full_attention)(q, k, v)
        out[f"out_max_abs_diff_vs_{tag}"] = worst(got, ref)
        out[f"grad_max_abs_diff_vs_{tag}"] = max(
            worst(a, b) for a, b in zip(got_grad, ref_grad))
    return out


def phase_train_transformer():
    """transformer_ring on one device at window 256 (the only trunk
    that reaches fused_window_attention), float32 then bfloat16."""
    rows, ok = {}, True
    for dtype_name, minibatches in (("float32", 8), ("bfloat16", 4)):
        # float32 with the default 4 minibatches does not fit one chip's
        # HBM at this shape (XLA: "Used 17.10G of 15.75G hbm"), so the
        # f32 run takes 8 — the kernel shape (256, 256, 4, 32) is the same
        config = flagship_config(
            policy="transformer_ring", policy_dtype=dtype_name,
            window_size=SIZES["tr_window"], num_envs=SIZES["tr_envs"],
            ppo_minibatches=minibatches, rollout_collect_dtype=(
                "float32" if dtype_name == "float32" else "bfloat16"),
        )
        state, _metrics, info, _step = run_steps(make_trainer(config), 2)
        parity = _attention_parity(dtype_name)
        good = (
            info["tpu_custom_calls"] >= 1 and all_finite(state.params)
            and all(x == x for x in info["loss"]) and parity["finite"]
        )
        if dtype_name == "float32":
            # the bound of tests/test_ops.py (fused vs full_attention)
            good = good and parity["out_max_abs_diff_vs_highest"] <= 2e-5 \
                and parity["grad_max_abs_diff_vs_highest"] <= 2e-5
        rows[dtype_name] = {"ok": good, "ppo_minibatches": minibatches,
                            **info, **parity}
        ok = ok and good
        del state
        gc.collect()
    return {"ok": ok, "envs": SIZES["tr_envs"], "window": SIZES["tr_window"],
            "attention_shape": [SIZES["tr_envs"], SIZES["tr_window"], 4, 32],
            **rows}


def phase_train_lstm():
    """256-wide LSTM on the three-month M1 series (generated from a
    seed, git-ignored): one phase with a tape of real length on device."""
    import jax
    import numpy as np

    sys.path.insert(0, str(REPO / "tools"))
    from make_example_data import ensure_m1_quarter

    from gymfx_tpu.data import native_loader

    t0 = time.perf_counter()
    path = ensure_m1_quarter()
    gen_s = time.perf_counter() - t0
    config = flagship_config(
        input_data_file=str(path), policy="lstm",
        num_envs=SIZES["lstm_envs"],
    )
    t0 = time.perf_counter()
    trainer = make_trainer(config)
    load_s = time.perf_counter() - t0
    state, _metrics, info, _step = run_steps(trainer, 2)
    carry = [np.asarray(x) for x in
             jax.tree.leaves(jax.device_get(state.policy_carry))]
    ok = (
        all(x == x for x in info["loss"]) and all_finite(state.params)
        and len(carry) == 2
        and all(c.shape == (SIZES["lstm_envs"], 256) for c in carry)
        and all(np.isfinite(c.astype(np.float32)).all() for c in carry)
    )
    return {
        "ok": ok, "envs": SIZES["lstm_envs"], "policy": "lstm 256 bf16",
        "n_bars_on_device": int(trainer.env.cfg.n_bars),
        "data_generate_s": round(gen_s, 2), "data_load_s": round(load_s, 2),
        "csv_loader": native_loader.served_by(path),
        "carry_shapes": [list(c.shape) for c in carry], **info,
    }


def phase_venue_lob():
    """venue=lob with the compiled stream matcher against the argsort
    engine: the kernel alone (exact int32) and two PPO steps."""
    import jax
    import numpy as np

    from gymfx_tpu.lob.book import empty_book, process_stream
    from gymfx_tpu.lob.flow import random_message_streams
    from gymfx_tpu.lob.scenarios import scenario_flow_params
    from gymfx_tpu.ops.lob_match import fused_process_stream

    books, depth, slots = SIZES["lob_envs"], SIZES["lob_depth"], SIZES["lob_slots"]
    msgs = random_message_streams(
        jax.random.PRNGKey(17), books, 256, scenario_flow_params("lob_calm")
    )
    book = empty_book(depth, slots)
    fused = jax.jit(jax.vmap(
        lambda m: fused_process_stream(book, m, interpret=False)))
    t0 = time.perf_counter()
    compiled = fused.lower(msgs).compile()
    kernel_compile_s = time.perf_counter() - t0
    calls = kernel_calls(compiled)
    got = compiled(msgs)
    ref = jax.jit(jax.vmap(lambda m: process_stream(book, m)))(msgs)
    stream_exact, _, stream_leaves = tree_diff(got, ref)
    fills = int(np.asarray(jax.device_get(ref[1].filled_qty)).sum())

    lob = dict(venue="lob", num_envs=books, lob_depth_levels=depth,
               lob_queue_slots=slots, ppo_horizon=SIZES["lob_horizon"])
    on = run_steps(make_trainer(flagship_config(lob_match_kernel="on", **lob)), 2)
    off = run_steps(make_trainer(flagship_config(lob_match_kernel="off", **lob)), 2)
    ok, cmp = compare_runs(on, off, loss_rtol=1e-6)
    ok = (
        ok and stream_exact and calls >= 1 and fills > 0
        and on[2]["tpu_custom_calls"] >= 1 and off[2]["tpu_custom_calls"] == 0
        and cmp["env_states_bitwise"]
    )
    return {
        "ok": ok, "books": books, "depth": depth, "slots": slots,
        "horizon": SIZES["lob_horizon"], "stream_messages": 256, "stream_kernel_compile_s": round(kernel_compile_s, 3),
        "stream_tpu_custom_calls": calls,
        "stream_exact_int32_vs_process_stream": stream_exact,
        "stream_leaves_differing": stream_leaves, "stream_lots_filled": fills,
        **on[2], **cmp,
    }


def phase_data_decode():
    """--data_compress on --feed scengen --scengen_snap_to_tick: the CLI
    episode streamed through compressed shards, and one shard decoded by
    the compiled decode_q16_block against decode_q16_ref."""
    import jax

    from gymfx_tpu.app.main import main as cli_main
    from gymfx_tpu.config import DEFAULT_VALUES
    from gymfx_tpu.data import compress as C
    from gymfx_tpu.data.feed import BarStreamer, market_data_nbytes
    from gymfx_tpu.scengen.feed import ScenGenDataset

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    summaries = {}
    for mode in ("on", "off"):
        out = OUT_DIR / f"decode_cli_{mode}.json"
        argv = [
            "--feed", "scengen", "--scengen_snap_to_tick",
            "--scengen_bars", "4096", "--driver_mode", "buy_hold",
            "--steps", str(SIZES["cli_steps"]), "--results_file", str(out),
            "--quiet_mode",
        ]
        if mode == "on":
            argv += ["--data_compress", "on", "--stream_hbm_budget_mb", "0.25"]
        summaries[mode] = json.loads(json.dumps(cli_main(argv), default=str))
    cli_identical = summaries["on"] == summaries["off"]

    window = SIZES["window"]
    cfg = dict(DEFAULT_VALUES)
    cfg.update(
        feed="scengen", scengen_preset="regime_mix",
        scengen_bars=SIZES["decode_bars"], scengen_seed=0,
        scengen_snap_to_tick=True, window_size=window,
        scengen_start="2024-03-17",
    )
    host = ScenGenDataset(cfg).build_market_data(window_size=window, device=False)
    streamer = BarStreamer(
        host, window_size=window,
        budget_mb=market_data_nbytes(host) / 8 / 2**20,
        compress="on", tick_size=float(cfg.get("lob_tick_size") or 1e-5),
    )
    tape = streamer.tape
    slab = C.shard_arrays(tape, 0)
    t0 = time.perf_counter()
    compiled = C.make_shard_decoder(tape, "on").lower(slab).compile()
    compile_s = time.perf_counter() - t0
    calls = kernel_calls(compiled)
    t0 = time.perf_counter()
    got = compiled(slab)
    jax.block_until_ready(got)
    decode_s = time.perf_counter() - t0
    ref = C.make_shard_decoder(tape, "off")(slab)
    bitwise, worst, leaves = tree_diff(got, ref)
    ok = cli_identical and bitwise and calls >= 1
    return {
        "ok": ok, "tape_bars": SIZES["decode_bars"],
        "shard_bars": int(streamer.shard_bars), "shards": int(streamer.num_shards),
        "compression_ratio": round(float(streamer.compression_ratio), 3),
        "decode_compile_s": round(compile_s, 3), "decode_shard_s": round(decode_s, 5),
        "tpu_custom_calls": calls, "bitwise_vs_decode_q16_ref": bitwise,
        "max_abs_diff": worst, "leaves_differing": leaves,
        "cli_results_identical_on_vs_off": cli_identical,
    }


def phase_serve():
    """engine_from_config (LSTM, default bucket ladder, session slots,
    warm boot) -> MicroBatcher.submit: 512 decisions over 64 sessions,
    against the jitted unbatched policy on the same rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gymfx_tpu.config import DEFAULT_VALUES
    from gymfx_tpu.serve import batcher_from_config, engine_from_config

    sessions = SIZES["serve_sessions"]
    rounds = SIZES["serve_decisions"] // sessions
    config = dict(DEFAULT_VALUES)
    config.update(
        input_data_file=SAMPLE_CSV, policy="lstm", window_size=SIZES["window"],
        serve_session_slots=4096,
    )
    t0 = time.perf_counter()
    bundle = engine_from_config(config)  # warm: every bucket compiles here
    engine = bundle.engine
    boot_s = time.perf_counter() - t0

    base = np.asarray(bundle.encode(bundle.reset_obs), engine.obs_dtype)
    rng = np.random.default_rng(0)
    rows = base[None, None] + 0.05 * rng.standard_normal(
        (rounds, sessions, *engine.obs_shape)).astype(engine.obs_dtype)

    batcher = batcher_from_config(engine, config)
    served, latencies = [], []
    t0 = time.perf_counter()
    try:
        for r in range(rounds):
            t_r = time.perf_counter()
            futures = [
                batcher.submit(rows[r, s], session=f"smoke-{s}")
                for s in range(sessions)
            ]
            served.append([f.result(timeout=120) for f in futures])
            latencies.append(time.perf_counter() - t_r)
    finally:
        batcher.close(timeout=30)
    serve_s = time.perf_counter() - t0

    # reference: the jitted unbatched policy, one session at a time,
    # each threading its own carry
    naive = jax.jit(engine.policy.apply_seq)
    ref_value = np.zeros((rounds, sessions), np.float32)
    ref_actor = []
    for s in range(sessions):
        carry = engine.policy.initial_carry(())
        per_round = []
        for r in range(rounds):
            actor, value, carry = naive(
                engine.params, jnp.asarray(rows[r, s]), carry)
            per_round.append(np.asarray(actor, np.float32))
            ref_value[r, s] = float(np.asarray(value, np.float32))
        ref_actor.append(per_round)
    ref_actor = np.asarray(ref_actor).transpose(1, 0, 2)   # (rounds, S, A)
    got_value = np.asarray(
        [[np.asarray(d.value, np.float32) for d in row] for row in served])
    got_actor = np.asarray(
        [[np.asarray(d.actor_out, np.float32) for d in row] for row in served])
    got_action = np.asarray([[int(d.action) for d in row] for row in served])
    value_diff = float(np.max(np.abs(got_value.reshape(rounds, sessions) - ref_value)))
    actor_diff = float(np.max(np.abs(got_actor - ref_actor)))
    agree = float(np.mean(got_action == np.argmax(ref_actor, axis=-1)))
    stats = engine.slot_stats()
    ok = (
        engine.late_compiles == 0 and engine.batch_mode == "matmul"
        and bool(engine.donate) and stats["enabled"]
        and stats["slot_decisions"] >= rounds * sessions
        and value_diff <= 5e-2 and actor_diff <= 5e-2 and agree >= 0.95
        and bool(np.isfinite(got_value).all())
    )
    return {
        "ok": ok, "policy": "lstm 256 f32", "buckets": list(engine.buckets),
        "batch_mode": engine.batch_mode, "donation": bool(engine.donate),
        "session_slots": 4096, "boot_s": round(boot_s, 3),
        "decisions": rounds * sessions, "sessions": sessions,
        "serve_s": round(serve_s, 4),
        "round_latency_s_median": round(float(np.median(latencies)), 5),
        "late_compiles": int(engine.late_compiles),
        "dispatches": int(batcher.dispatches),
        "slot_decisions": int(stats["slot_decisions"]),
        "value_max_abs_diff_vs_unbatched": value_diff,
        "actor_out_max_abs_diff_vs_unbatched": actor_diff,
        "action_agreement": agree,
    }


def phase_cli():
    """The README quick-start episode, then a 3-superstep --mode training
    run with a checkpoint and a --resume_training run that continues it."""
    import shutil

    from gymfx_tpu.app.main import main as cli_main

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    episode_file = OUT_DIR / "cli_episode.json"
    t0 = time.perf_counter()
    episode = cli_main([
        "--input_data_file", SAMPLE_CSV, "--driver_mode", "buy_hold",
        "--steps", str(SIZES["cli_steps"]), "--results_file", str(episode_file),
        "--quiet_mode",
    ])
    episode_s = time.perf_counter() - t0

    ckpt = OUT_DIR / "cli_ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    envs, horizon, k = SIZES["cli_envs"], SIZES["horizon"], 2
    per_superstep = envs * horizon * k
    train_argv = [
        "--mode", "training", "--input_data_file", SAMPLE_CSV,
        "--num_envs", str(envs), "--ppo_horizon", str(horizon),
        "--supersteps_per_dispatch", str(k),
        "--train_total_steps", str(3 * per_superstep),
        "--checkpoint_dir", str(ckpt), "--quiet_mode",
    ]
    t0 = time.perf_counter()
    first = cli_main(train_argv + [
        "--results_file", str(OUT_DIR / "cli_train.json")])
    train_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    resumed = cli_main(train_argv + [
        "--resume_training", "--results_file",
        str(OUT_DIR / "cli_resume.json")])
    resume_s = time.perf_counter() - t0

    steps_saved = sorted(
        int(p.name) for p in ckpt.iterdir() if p.name.isdigit())
    first_steps = int(first["train_metrics"]["total_env_steps"])
    resumed_steps = int(resumed["train_metrics"]["total_env_steps"])
    ok = (
        episode_file.exists() and "final_equity" in json.dumps(episode)
        and (OUT_DIR / "cli_train.json").exists()
        and (OUT_DIR / "cli_resume.json").exists()
        and first_steps == 3 * per_superstep
        and first_steps in steps_saved
        and first_steps + resumed_steps in steps_saved
    )
    return {
        "ok": ok, "episode_s": round(episode_s, 3),
        "episode_steps": SIZES["cli_steps"],
        "train_s": round(train_s, 3), "resume_s": round(resume_s, 3),
        "train_env_steps": first_steps, "resumed_env_steps": resumed_steps,
        "checkpoint_steps": steps_saved,
        "resume_continued_from": first_steps,
        "train_loss": first["train_metrics"].get("loss"),
        "resume_loss": resumed["train_metrics"].get("loss"),
    }


# ---------------------------------------------------------------------------
# the four-chip phase (--chips 4): ShardedRuntime on {"data": 4}
# ---------------------------------------------------------------------------
def phase_sharded():
    """PPO MLP at 8192 envs through ShardedRuntime on mesh {"data": 4}
    (the path of tools/multichip_bench.py) against the same global batch
    on one device, 4 steps each."""
    import re

    import jax

    from gymfx_tpu.parallel import make_mesh

    config = flagship_config(num_envs=SIZES["sharded_envs"])
    single = run_steps(make_trainer(config), 4)
    mesh = make_mesh({"data": 4})
    trainer = make_trainer(config, mesh=mesh)
    placed = trainer.init_state(0)

    def holders(tree):
        return sorted({
            d.id for leaf in jax.tree.leaves(tree)
            if getattr(leaf, "ndim", 0) >= 1
            for d in leaf.sharding.device_set
            if not leaf.sharding.is_fully_replicated
        })

    env_devices = holders(placed.env_states)
    obs_devices = holders(placed.obs_vec)
    param_devices = sorted({
        d.id for leaf in jax.tree.leaves(placed.params)
        for d in leaf.sharding.device_set})
    del placed
    sharded = run_steps(trainer, 4)
    collectives = sorted(set(re.findall(
        r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)",
        sharded[3].as_text())))
    loss_single = [float(m["loss"]) for m in single[1]]
    loss_sharded = [float(m["loss"]) for m in sharded[1]]
    first = abs(loss_single[0] - loss_sharded[0])
    ok = (
        len(env_devices) == 4 and len(obs_devices) == 4
        and len(param_devices) == 4 and "all-reduce" in collectives
        and first <= 1e-3 * max(1.0, abs(loss_single[0]))
        and all(x == x for x in loss_sharded)
        and all_finite(sharded[0].params)
    )
    return {
        "ok": ok, "envs": SIZES["sharded_envs"], "mesh_shape": {"data": 4},
        "env_state_shard_devices": env_devices,
        "obs_shard_devices": obs_devices,
        "params_devices": param_devices, "collectives": collectives,
        "loss_single_device": loss_single, "loss_sharded": loss_sharded,
        "first_step_loss_abs_diff": first,
        "single": {k: single[2][k] for k in
                   ("compile_s", "first_step_s", "steady_step_s")},
        "sharded": {k: sharded[2][k] for k in
                    ("compile_s", "first_step_s", "steady_step_s")},
    }


ONE_CHIP_PHASES = [
    ("train_mlp", phase_train_mlp),
    ("train_mlp_features", phase_train_mlp_features),
    ("train_transformer", phase_train_transformer),
    ("train_lstm", phase_train_lstm),
    ("venue_lob", phase_venue_lob),
    ("data_decode", phase_data_decode),
    ("serve", phase_serve),
    ("cli", phase_cli),
]
FOUR_CHIP_PHASES = [("sharded", phase_sharded)]


def run_phase(name, fn) -> bool:
    """Run one phase and print its line.  A failure is recorded (with the
    traceback on stderr) and the run goes on so that one chip call shows
    every fault; the script still exits non-zero."""
    import jax

    t0 = time.perf_counter()
    try:
        out = dict(fn())
        ok = bool(out.pop("ok"))
    except Exception as exc:  # recorded, never forgiven: see main()
        traceback.print_exc()
        out, ok = {"error": f"{type(exc).__name__}: {exc}"[:3000]}, False
    emit({"phase": name, "ok": ok,
          "seconds": round(time.perf_counter() - t0, 2), **out})
    gc.collect()
    jax.clear_caches()
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the ShardedRuntime phase on a 2x2 host")
    args = ap.parse_args(argv)

    from gymfx_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()

    # ---- phase `device`: first thing, before any work -----------------
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    device_ok = device["platform"] == "tpu" and len(devices) >= args.chips
    from gymfx_tpu.bench_util import PEAK_BF16_FLOPS

    emit({"phase": "device", "ok": device_ok, **device,
          "chips_asked": args.chips, "jax": jax.__version__,
          "compile_cache_dir": cache_dir,
          "peak_bf16_flops": PEAK_BF16_FLOPS.get(device["kind"])})
    if not device_ok:
        emit({"ok": False, "device": device})
        return 2

    phases = FOUR_CHIP_PHASES if args.chips == 4 else ONE_CHIP_PHASES
    results = [run_phase(name, fn) for name, fn in phases]
    ok = all(results)
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
