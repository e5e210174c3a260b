#!/usr/bin/env python3
"""Throughput benchmark — prints ONE JSON line.

Workload: the north-star configuration (BASELINE.json) — PPO training
of the 3-layer MLP policy on the EUR/USD 1-min example bars, rollout
collection fused into the env scan, measured as env steps/sec on the
local accelerator.  vs_baseline compares against the target of
1M env steps/sec on a v5p-8 (8 cores) = 125k steps/sec/chip.

Usage: python bench.py [--n_envs N] [--horizon T] [--iters K] [--quick]
"""
import argparse
import sys

from gymfx_tpu.compile_cache import enable_compile_cache

enable_compile_cache()


def lob_main(args) -> None:
    """``--lob``: matching-engine fills/sec depth sweep — one
    schema-valid ``lob_fills_per_sec`` JSON line (the venue's
    message-processing hot loop, no env/ledger around it).

    Workload: ``books`` independent message streams from the lob_calm
    flow mix (flow.random_message_streams — the SAME streams the
    4096-way parity test replays through the Python oracle), each
    scanned through a fresh fixed-capacity book under ``jit(vmap(...))``,
    repeated across ``--depths``.  The headline row is the venue's
    default depth (24 levels); every swept depth lands in
    ``depth_sweep``.
    """
    import time

    from gymfx_tpu.bench_util import probe_device

    probe_device()

    import jax
    import jax.numpy as jnp

    from gymfx_tpu.lob.book import empty_book, process_stream
    from gymfx_tpu.lob.flow import random_message_streams
    from gymfx_tpu.lob.scenarios import scenario_flow_params

    books, messages, iters = args.books, args.messages, args.iters
    depths = [int(d) for d in args.depths.split(",") if d.strip()]
    if args.quick:
        books, messages, iters, depths = 256, 64, 2, [8, 24]
    queue_slots = 4  # the venue default (config/defaults.py)
    fp = scenario_flow_params("lob_calm")
    key = jax.random.PRNGKey(0)

    # r10: route the sweep through the pallas matcher (ops/lob_match.py)
    # instead of the XLA oracle scan — off|on|interpret resolves in
    # ops/dispatch like every kernel switch; exact int32 parity is
    # pinned by tests/test_lob_match_kernel.py so both paths count the
    # same fills
    from gymfx_tpu.ops.dispatch import kernel_interpret

    interp = kernel_interpret(args.lob_match_kernel)
    if interp is not None:
        from gymfx_tpu.ops.lob_match import fused_process_stream

        def _stream(book, m):
            return fused_process_stream(book, m, interpret=interp)
    else:
        _stream = process_stream

    sweep = {}
    for depth in depths:
        msgs = jax.block_until_ready(
            random_message_streams(key, books, messages, fp)
        )

        @jax.jit
        def run(ms, depth=depth):
            return jax.vmap(
                lambda m: _stream(empty_book(depth, queue_slots), m)
            )(ms)

        book, fills = run(msgs)  # compile + warmup
        jax.block_until_ready(book)
        events = int(jnp.sum(fills.fill_events))
        t0 = time.perf_counter()
        for _ in range(iters):
            book, fills = run(msgs)
        jax.block_until_ready(book)
        dt = time.perf_counter() - t0
        per_dispatch = dt / iters
        sweep[str(depth)] = {
            "fills_per_sec": round(events / per_dispatch, 1),
            "msgs_per_sec": round(books * messages / per_dispatch, 1),
            "match_ms": round(per_dispatch * 1e3, 3),
            "fill_events_per_dispatch": events,
        }

    headline_depth = 24 if "24" in sweep else depths[0]
    head = sweep[str(headline_depth)]
    from gymfx_tpu.bench_util import emit_bench_record

    # shared row helper (r10): the analytic-MFU key block rides on every
    # bench row — null here (integer matching has no dense-GEMM FLOP
    # model) but the KEY SET matches the trainer rows, so dashboards
    # parse one schema
    emit_bench_record(
        {
            "metric": "lob_fills_per_sec",
            "value": head["fills_per_sec"],
            "unit": (
                "fills/sec/chip (vmapped LOB matching, "
                f"depth={headline_depth}x{queue_slots} slots, "
                "lob_calm flow mix)"
            ),
            "fills_per_sec_per_chip": head["fills_per_sec"],
            "msgs_per_sec": head["msgs_per_sec"],
            "match_ms": head["match_ms"],
            "books": books,
            "depth_levels": headline_depth,
            "queue_slots": queue_slots,
            "messages_per_stream": messages,
            "lob_match_kernel": args.lob_match_kernel,
            "depth_sweep": sweep,
        },
        step_time_s=head["match_ms"] / 1e3,
        device=jax.devices()[0],
    )


def scengen_main(args) -> None:
    """``--scengen``: generative scenario engine bars/sec sweep — one
    schema-valid ``scengen_bars_per_sec`` JSON line (docs/scenarios.md).

    Workload: the full generation dispatch (shock draws + the scanned
    regime/overlay transform, engine.generate) per preset at a fixed
    (n_bars, n_assets) shape; the headline row is the first preset in
    ``--scengen_presets`` and every preset lands in ``preset_sweep``.
    """
    import time

    from gymfx_tpu.bench_util import probe_device

    probe_device()

    import jax

    from gymfx_tpu.scengen.engine import generate
    from gymfx_tpu.scengen.params import scenario_params

    n_bars, n_assets, iters = (
        args.scengen_bars, args.scengen_assets, args.iters
    )
    presets = [p for p in args.scengen_presets.split(",") if p.strip()]
    if args.quick:
        n_bars, n_assets, iters = 4096, 1, 2
        presets = ["regime_mix", "flash_crash"]
    key = jax.random.PRNGKey(0)

    sweep = {}
    for preset in presets:
        p = scenario_params(preset)
        paths = generate(p, key, n_bars, n_assets)  # compile + warmup
        jax.block_until_ready(paths.close)
        t0 = time.perf_counter()
        for _ in range(iters):
            paths = generate(p, key, n_bars, n_assets)
        jax.block_until_ready(paths.close)
        per_dispatch = (time.perf_counter() - t0) / iters
        sweep[preset] = {
            "bars_per_sec": round(n_bars * n_assets / per_dispatch, 1),
            "gen_ms": round(per_dispatch * 1e3, 3),
        }

    head = sweep[presets[0]]
    from gymfx_tpu.bench_util import emit_bench_record

    emit_bench_record(
        {
            "metric": "scengen_bars_per_sec",
            "value": head["bars_per_sec"],
            "unit": (
                "generated bars/sec/chip (scanned regime/overlay "
                f"transform, {n_assets} asset(s), "
                f"preset={presets[0]})"
            ),
            "bars_per_sec_per_chip": head["bars_per_sec"],
            "gen_ms": head["gen_ms"],
            "n_bars": n_bars,
            "n_assets": n_assets,
            "preset": presets[0],
            "preset_sweep": sweep,
        },
        step_time_s=head["gen_ms"] / 1e3,
        device=jax.devices()[0],
    )


def _stream_probe(data_compress: str, n_bars: int) -> dict:
    """Billion-bar data path probe (docs/performance.md): stream a
    tick-snapped generated tape through the compressed BarStreamer and
    report decode throughput plus the resident-bars win over the
    uncompressed double buffer at the SAME HBM budget.

    All four headline keys are null with ``--data_compress off`` — the
    probe only runs when the compressed path is requested, so the
    default bench row is byte-identical to previous rounds.
    """
    keys = (
        "stream_bars_per_sec", "data_compression_ratio",
        "resident_bars", "resident_bars_uncompressed",
    )
    if data_compress == "off":
        return {k: None for k in keys}
    import time

    import jax

    from gymfx_tpu.config import DEFAULT_VALUES
    from gymfx_tpu.data.feed import BarStreamer, market_data_nbytes
    from gymfx_tpu.scengen.feed import ScenGenDataset

    window = 32
    cfg = dict(DEFAULT_VALUES)
    cfg.update(
        feed="scengen", scengen_preset="regime_mix",
        scengen_bars=int(n_bars), scengen_seed=0,
        # generated prices snapped onto the LOB int-tick grid in f64,
        # BEFORE the f32 cast — the int16 tick-delta wire format's
        # on-grid requirement (scengen/feed.py)
        scengen_snap_to_tick=True, window_size=window,
        # a DST-free window (between the March and November US shifts):
        # NY-calendar columns are weekly-periodic inside it, so they
        # compress to one-week lookup tables; a tape crossing a DST
        # shift keeps correctness by falling back to q16 deltas for
        # those columns at ~0.7x the ratio (DIVERGENCES.md)
        scengen_start="2024-03-17",
    )
    tick = float(cfg.get("lob_tick_size") or 1e-5)
    host = ScenGenDataset(cfg).build_market_data(
        window_size=window, device=False
    )
    # budget = 1/8 of the decoded tape: both modes must stream (the
    # compressed ring must not swallow the whole tape, or the resident
    # comparison degenerates to "everything fits")
    budget_mb = market_data_nbytes(host) / 8 / 2**20
    bs = BarStreamer(
        host, window_size=window, budget_mb=budget_mb,
        compress=data_compress, tick_size=tick,
    )
    bs_off = BarStreamer(
        host, window_size=window, budget_mb=budget_mb,
        compress="off", tick_size=tick,
    )
    jax.block_until_ready(bs._device_shard(0).close)  # compile + warmup
    t0 = time.perf_counter()
    shard = None
    for k in range(bs.num_shards):
        shard = bs._device_shard(k)
    jax.block_until_ready(shard.close)
    dt = time.perf_counter() - t0
    return {
        "stream_bars_per_sec": round(bs.num_shards * bs.shard_bars / dt, 1),
        "data_compression_ratio": round(bs.compression_ratio, 3),
        "resident_bars": int(bs.resident_bars),
        "resident_bars_uncompressed": int(bs_off.resident_bars),
        "stream_hbm_budget_mb": round(budget_mb, 3),
        "stream_tape_bars": int(n_bars),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n_envs", type=int, default=8192)
    ap.add_argument("--horizon", type=int, default=64)
    # default 20 per bench_util.DEFAULT_BENCH_ITERS (dispatch-latency
    # amortization — the round-3 "headline regression" was 5-iter noise)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument(
        "--supersteps", type=int, default=1,
        help="train steps fused per dispatch (superstep driver; 1 = "
             "per-step dispatch)",
    )
    ap.add_argument("--quick", action="store_true", help="small shapes (CI)")
    ap.add_argument(
        "--rollout_env_kernel", choices=["off", "on", "interpret"],
        default="on",
        help="fused env-dynamics pallas kernels in the rollout scan "
             "(ops/env_dynamics.py; 'on' is the compiled kernel on a "
             "TPU and the plain-XLA twin on a CPU, 'interpret' runs "
             "the kernels in pallas interpret mode on any backend — "
             "the CI parity path)",
    )
    ap.add_argument(
        "--data_compress", choices=["off", "on", "interpret"],
        default="off",
        help="also run the billion-bar streaming probe: int16 tick-delta "
             "tape + fused on-device decode (data/compress.py) vs the "
             "uncompressed double buffer at the same HBM budget; adds "
             "the stream_bars_per_sec / data_compression_ratio / "
             "resident_bars keys (null when off)",
    )
    ap.add_argument(
        "--stream_bars", type=int, default=229376,
        help="generated tape length for the --data_compress probe "
             "(weekly lookup tables amortize with length; the default "
             "is ~32 weeks of minute bars — within one DST regime, "
             "where the NY-calendar columns stay weekly-periodic; "
             "--quick shrinks this to 32768)",
    )
    ap.add_argument(
        "--trace", type=str, default=None, metavar="DIR",
        help="capture a managed jax.profiler trace of one fused step "
             "into a manifested capture bundle under DIR (read back "
             "with tools/profile_report.py, or view with tensorboard)",
    )
    # LOB matching-engine sweep (docs/lob.md)
    ap.add_argument(
        "--lob", action="store_true",
        help="benchmark the LOB matching engine instead of PPO "
             "(emits a lob_fills_per_sec record)",
    )
    ap.add_argument("--books", type=int, default=1024)
    ap.add_argument("--messages", type=int, default=256)
    ap.add_argument(
        "--lob_match_kernel", choices=["off", "on", "interpret"],
        default="off",
        help="route the --lob sweep through the pallas matching kernel "
             "(ops/lob_match.py) instead of the XLA oracle scan",
    )
    ap.add_argument(
        "--depths", type=str, default="8,16,24,48",
        help="comma-separated book depths for the --lob sweep",
    )
    # generative scenario engine sweep (docs/scenarios.md)
    ap.add_argument(
        "--scengen", action="store_true",
        help="benchmark the scenario generator instead of PPO "
             "(emits a scengen_bars_per_sec record)",
    )
    ap.add_argument("--scengen_bars", type=int, default=65536)
    ap.add_argument("--scengen_assets", type=int, default=4)
    ap.add_argument(
        "--scengen_presets", type=str,
        default="regime_mix,flash_crash,liquidity_drought,gap_open",
        help="comma-separated presets for the --scengen sweep "
             "(first = headline row)",
    )
    args = ap.parse_args()
    if args.lob:
        return lob_main(args)
    if args.scengen:
        return scengen_main(args)
    if args.quick:
        args.n_envs, args.horizon, args.iters = 256, 32, 2
        args.stream_bars = min(args.stream_bars, 32768)

    from gymfx_tpu.bench_util import probe_device

    probe_device()

    import jax

    from gymfx_tpu.config import DEFAULT_VALUES
    from gymfx_tpu.core.runtime import Environment
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from

    config = dict(DEFAULT_VALUES)
    config.update(
        input_data_file="examples/data/eurusd_sample.csv",
        num_envs=args.n_envs,
        ppo_horizon=args.horizon,
        ppo_epochs=1,
        ppo_minibatches=4,
        policy="mlp",
        # policy compute in bfloat16 (MXU-native; params/updates stay
        # f32) — measured ~10% faster than f32 at identical loss curves
        policy_dtype="bfloat16",
        # trajectory (env-permuted) minibatches: contiguous update-phase
        # DMA instead of the T*N random sample gather — measured 12.4M
        # vs 8.3M steps/s at 8192 envs with identical held-out learning
        # (train/ppo.py minibatch_scheme; r5 closes the wide-batch
        # rollover this way: 32k envs sustain 12.5M)
        ppo_minibatch_scheme="env_permute",
        window_size=32,
        # rollout hot-path (r6): bf16 trajectory obs storage, halving
        # the widest collected buffer's HBM write+read traffic
        # (docs/performance.md).  The fused obs kernel is NOT here: the
        # sample CSV has no feature columns (n_features == 0), so there
        # is no feature window to scale and rollout_obs_kernel="on" is
        # refused by EnvConfig — chip_smoke.py's train_mlp_features
        # phase is where that kernel runs
        rollout_collect_dtype="bfloat16",
        # env-dynamics hot path (r10): the reward/broker scan's
        # fill/bracket and mark/reward passes as fused pallas kernels
        # bracketing the strategy kernel (oracle: the plain-XLA step,
        # tests/test_env_dynamics_kernel.py); "on" is the compiled
        # kernel on a TPU and the XLA twin on a CPU (ops/dispatch.py)
        rollout_env_kernel=args.rollout_env_kernel,
    )
    env = Environment(config)
    trainer = PPOTrainer(env, ppo_config_from(config))

    from gymfx_tpu.bench_util import (
        measure_phase_split,
        measure_train_many,
        measure_train_step,
        mfu,
    )

    state = trainer.init_state(0)
    # always time the per-step dispatch path: it is both the K=1
    # headline and the baseline the superstep overhead is measured from
    dt1, step_flops, state, _step = measure_train_step(trainer, state, args.iters)
    per_step_single = dt1 / args.iters

    # phase attribution: rollout vs update halves timed as donated-carry
    # sub-programs off the same phase methods the fused step composes
    # (bench_util.measure_phase_split) — proves where the cycle goes
    rollout_ms = update_ms = update_gemm_frac = None
    split = measure_phase_split(trainer, state, args.iters)
    if split is not None:
        rollout_s, update_s, state, update_flops = split
        rollout_ms = rollout_s / args.iters * 1e3
        update_ms = update_s / args.iters * 1e3
        # share of the whole step's XLA cost-model FLOPs spent in the
        # update phase (the GEMM chain) — the ceiling on what the r10
        # rollout/update overlap can hide
        if update_flops and step_flops:
            update_gemm_frac = min(1.0, update_flops / step_flops)

    if args.trace:
        # one traced fused step through the managed capture path: the
        # bundle manifest reuses the already-compiled executable (HLO
        # scope map + cost-model FLOPs) and the phase split measured
        # above — zero extra compiles vs the raw start/stop_trace
        from gymfx_tpu.telemetry.ledger import config_digest
        from gymfx_tpu.telemetry.profiler import ProfilerSession

        session = ProfilerSession(
            args.trace, config_sha256=config_digest(dict(config))
        )

        def _trace_workload(it_start, k):
            info = {
                "algo": "ppo", "n_envs": args.n_envs,
                "horizon": args.horizon,
                "steps_per_iter": args.n_envs * args.horizon,
                "xla_flops_per_dispatch": step_flops,
                "xla_flops_per_step": step_flops,
                "phase_split": (
                    {"rollout_ms": rollout_ms, "update_ms": update_ms,
                     "iters": args.iters, "source": "measure_phase_split"}
                    if rollout_ms is not None else None
                ),
            }
            try:
                info["hlo_text"] = _step.as_text()
            except Exception:
                pass
            return info

        session.set_workload_source(_trace_workload)
        with session.capture(label="bench_trace") as cap:
            state, _m = _step(state)
            jax.block_until_ready(state)
        if cap.bundle:
            print(f"# trace capture bundle: {cap.bundle}")

    K = max(1, args.supersteps)
    baseline_per_chip = 1_000_000 / 8  # BASELINE.json: 1M steps/s on v5p-8
    steps_per_iter = args.n_envs * args.horizon
    overlap_ms_saved = None
    if K > 1:
        # same number of timed dispatches, each covering K train steps
        dtK, dispatch_flops, state, _ = measure_train_many(
            trainer, state, args.iters, K
        )
        per_step = dtK / (args.iters * K)
        steps_per_sec = steps_per_iter / per_step
        util = mfu(dispatch_flops, args.iters, dtK, jax.devices()[0])
        # fraction of per-step wall time that was host dispatch/sync
        # overhead, eliminated by fusing K steps into one dispatch
        overhead = max(0.0, 1.0 - per_step / per_step_single)

        # r10 overlap driver: the same K-step superstep with iteration
        # i's rollout issued alongside iteration i-1's update GEMMs
        # (train/common.make_train_many_overlapped — opt-in one-update-
        # stale rollout params).  Reported as per-train-step ms saved vs
        # the sequential superstep; null at K=1 (no overlap body runs)
        from gymfx_tpu.train.ppo import PPOTrainer as _PPOTrainer

        trainer_ovl = _PPOTrainer(
            env, ppo_config_from(dict(config, superstep_overlap=True))
        )
        dtO, _oflops, _ostate, _ = measure_train_many(
            trainer_ovl, trainer_ovl.init_state(0), args.iters, K
        )
        overlap_ms_saved = (per_step - dtO / (args.iters * K)) * 1e3
    else:
        steps_per_sec = steps_per_iter / per_step_single
        util = mfu(step_flops, args.iters, dt1, jax.devices()[0])
        overhead = None

    # analytic cross-check of the XLA cost-model MFU: closed-form FLOPs
    # from the policy's parameter shapes (telemetry/mfu.py), plus device
    # memory accounting — keys are always present, null off-TPU
    from gymfx_tpu.telemetry.mfu import analytic_train_step_flops

    analytic = analytic_train_step_flops(
        state.params,
        num_envs=args.n_envs,
        horizon=args.horizon,
        update_epochs=int(config["ppo_epochs"]),
    )
    per_step_s = per_step if K > 1 else per_step_single
    from gymfx_tpu.bench_util import emit_bench_record

    emit_bench_record(
        {
            "metric": "ppo_env_steps_per_sec_per_chip",
            "value": round(steps_per_sec, 1),
            "unit": "env steps/sec/chip (PPO MLP bf16 policy, fused "
                    "rollout+update, env-permuted minibatches)",
            "vs_baseline": round(steps_per_sec / baseline_per_chip, 3),
            # XLA cost-model FLOPs / public peak bf16 chip FLOPs
            # (gymfx_tpu/bench_util.py); null off-TPU
            "mfu": round(util, 5) if util is not None else None,
            "supersteps": K,
            # per-train-step host overhead removed by the superstep
            # driver: 1 - (superstep per-step time / single-dispatch
            # per-step time); null at K=1 (nothing to compare)
            "dispatch_overhead_frac": (
                round(overhead, 4) if overhead is not None else None
            ),
            "per_step_ms_single_dispatch": round(per_step_single * 1e3, 3),
            # rollout/update phase attribution (donated-carry
            # sub-programs; sums slightly above the fused step —
            # read them as a ratio, not an absolute)
            "rollout_ms": (
                round(rollout_ms, 3) if rollout_ms is not None else None
            ),
            "update_ms": (
                round(update_ms, 3) if update_ms is not None else None
            ),
            # r10 overlap accounting: per-train-step ms the overlapped
            # superstep saves vs the sequential one (null at K=1), and
            # the update phase's share of whole-step FLOPs — the
            # overlap's theoretical ceiling
            "overlap_ms_saved": (
                round(overlap_ms_saved, 3)
                if overlap_ms_saved is not None else None
            ),
            "update_gemm_frac": (
                round(update_gemm_frac, 4)
                if update_gemm_frac is not None else None
            ),
            "rollout_env_kernel": args.rollout_env_kernel,
            # billion-bar data path probe (--data_compress; null when
            # off): compressed streaming decode throughput and the
            # resident-bars capacity vs the uncompressed double buffer
            # at the same stream_hbm_budget_mb
            **_stream_probe(args.data_compress, args.stream_bars),
        },
        analytic_flops=analytic,
        step_time_s=per_step_s,
        device=jax.devices()[0],
    )


if __name__ == "__main__":
    sys.exit(main())
