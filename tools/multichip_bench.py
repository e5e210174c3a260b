#!/usr/bin/env python3
"""Multichip scaling benchmark — prints ONE JSON line.

The mesh path used to be a loss-only dry run; this tool measures it.
The SAME fused PPO train step (the bench.py flagship workload shape) is
timed twice:

  * unsharded on a single device — the in-run single-device baseline;
  * sharded over a mesh of the local devices through the shared
    ``ShardedRuntime`` plan (env batch over 'data', params replicated /
    tensor-sharded, one donated GSPMD program).

and the record reports the aggregate env steps/sec across the mesh,
the per-chip rate, and

    scaling_efficiency = (aggregate / single_device) / n_devices

(1.0 = perfect strong scaling of the same global batch).  Per-phase rollout/update split and the analytic
per-chip MFU slice (telemetry/mfu.py) ride along, all validated by
``tools/bench_contract_schema.json`` (metric
``multichip_env_steps_per_sec``).

Usage:
  python tools/multichip_bench.py [--quick] [--n_envs N] [--horizon T]
                                  [--iters K] [--mesh_shape JSON]

On CPU, run with ``--xla_force_host_platform_device_count=8`` in
XLA_FLAGS (tests/conftest.py does) to get a virtual 8-device mesh.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from gymfx_tpu.compile_cache import enable_compile_cache

enable_compile_cache()


def _trainer(n_envs: int, horizon: int, mesh=None):
    from gymfx_tpu.config import DEFAULT_VALUES
    from gymfx_tpu.core.runtime import Environment
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from

    config = dict(DEFAULT_VALUES)
    config.update(
        input_data_file=str(
            Path(__file__).resolve().parent.parent
            / "examples/data/eurusd_sample.csv"
        ),
        num_envs=n_envs, ppo_horizon=horizon, ppo_epochs=1,
        ppo_minibatches=4, policy="mlp", policy_dtype="bfloat16",
        ppo_minibatch_scheme="env_permute", window_size=32,
    )
    env = Environment(config)
    return PPOTrainer(env, ppo_config_from(config), mesh=mesh), config


def build_record(*, n_envs: int, horizon: int, iters: int,
                 mesh_shape=None, measure_split: bool = True,
                 profile_dir=None) -> dict:
    """Measure single-device vs mesh-sharded throughput; returns the
    contract record (metric ``multichip_env_steps_per_sec``).
    ``measure_split=False`` skips the phase-split sub-programs (two
    extra AOT compiles) and reports null rollout/update — the CI quick
    path, where compile time dominates the whole measurement.  With
    ``profile_dir``, one sharded dispatch is trace-captured through the
    managed profiler path (manifested bundle off the same compiled
    executable — tools/profile_report.py reads it back)."""
    import jax

    from gymfx_tpu.bench_util import (
        measure_phase_split,
        measure_train_step,
    )
    from gymfx_tpu.parallel import ShardedRuntime, make_mesh
    from gymfx_tpu.telemetry.mfu import analytic_train_step_flops, mfu_report

    mesh = make_mesh(mesh_shape)
    runtime = ShardedRuntime(mesh)
    runtime.validate_batch(n_envs, "n_envs")
    n = runtime.n_devices
    device = jax.devices()[0]

    # in-run single-device baseline: same config, same global batch
    single, config = _trainer(n_envs, horizon)
    s_state = single.init_state(0)
    dt_s, _flops_s, s_state, _ = measure_train_step(single, s_state, iters)
    sps_single = n_envs * horizon * iters / dt_s
    del single, s_state

    # mesh-sharded run through the shared runtime plan (the compiled
    # executable is kept for the optional profiler capture below)
    sharded, _ = _trainer(n_envs, horizon, mesh=mesh)
    m_state = sharded.init_state(0)
    dt_m, flops_m, m_state, m_step = measure_train_step(
        sharded, m_state, iters
    )
    aggregate = n_envs * horizon * iters / dt_m
    per_step_s = dt_m / iters

    rollout_ms = update_ms = None
    split = measure_phase_split(sharded, m_state, iters) \
        if measure_split else None
    if split is not None:
        rollout_s, update_s, m_state, _u_flops = split
        rollout_ms = rollout_s / iters * 1e3
        update_ms = update_s / iters * 1e3

    # per-chip analytic MFU at mesh scale: the global step's closed-form
    # FLOPs split evenly over the mesh, against ONE chip's public peak
    analytic = analytic_train_step_flops(
        m_state.params, num_envs=n_envs, horizon=horizon,
        update_epochs=int(config["ppo_epochs"]),
    )
    report = mfu_report(analytic / n, per_step_s, device)

    if profile_dir is not None:
        # one trace-captured sharded dispatch off the same executable
        from gymfx_tpu.telemetry.ledger import config_digest
        from gymfx_tpu.telemetry.profiler import ProfilerSession

        session = ProfilerSession(
            str(profile_dir), config_sha256=config_digest(dict(config))
        )

        def _profile_workload(it_start, k):
            info = {
                "algo": "ppo_multichip", "n_envs": n_envs,
                "horizon": horizon, "steps_per_iter": n_envs * horizon,
                "n_devices": n, "mesh_shape": runtime.mesh_shape,
                "xla_flops_per_dispatch": flops_m,
                "xla_flops_per_step": flops_m,
                "analytic_flops_per_step": analytic,
                "phase_split": (
                    {"rollout_ms": rollout_ms, "update_ms": update_ms,
                     "iters": iters, "source": "measure_phase_split"}
                    if rollout_ms is not None else None
                ),
            }
            try:
                info["hlo_text"] = m_step.as_text()
            except Exception:
                pass
            return info

        session.set_workload_source(_profile_workload)
        with session.capture(label="multichip_bench"):
            m_state, _ = m_step(m_state)
            jax.block_until_ready(m_state)

    from gymfx_tpu.bench_util import stamp_comparability

    per_chip = aggregate / n
    efficiency = (aggregate / sps_single) / n
    return stamp_comparability({
        "metric": "multichip_env_steps_per_sec",
        "value": round(aggregate, 1),
        "unit": "aggregate env steps/sec across the mesh (PPO MLP bf16 "
                "policy, fused rollout+update, shared ShardedRuntime "
                "plan, one donated GSPMD superstep program)",
        "aggregate_steps_per_sec": round(aggregate, 1),
        "per_chip_steps_per_sec": round(per_chip, 1),
        "single_device_steps_per_sec": round(sps_single, 1),
        # strong scaling of the same global batch: 1.0 = ideal
        "scaling_efficiency": round(efficiency, 4),
        "n_devices": n,
        "mesh_shape": runtime.mesh_shape,
        "rollout_ms": round(rollout_ms, 3) if rollout_ms is not None else None,
        "update_ms": round(update_ms, 3) if update_ms is not None else None,
        # analytic per-chip FLOP model + memory accounting
        # (gymfx_tpu/telemetry/mfu.py); null where the backend cannot say
        **report,
    }, device=device)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n_envs", type=int, default=8192)
    ap.add_argument("--horizon", type=int, default=64)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--quick", action="store_true", help="small shapes (CI)")
    ap.add_argument(
        "--mesh_shape", type=str, default=None,
        help='JSON mesh shape, e.g. \'{"data": 4, "model": 2}\'; '
             "default: all local devices on the 'data' axis",
    )
    ap.add_argument(
        "--profile", metavar="DIR", default=None,
        help="capture one sharded dispatch into a manifested profiler "
             "bundle under DIR (tools/profile_report.py reads it back)",
    )
    args = ap.parse_args()
    if args.quick:
        args.n_envs, args.horizon = 256, 16
        args.iters = args.iters or 2
    if args.iters is None:
        from gymfx_tpu.bench_util import DEFAULT_BENCH_ITERS

        args.iters = DEFAULT_BENCH_ITERS

    from gymfx_tpu.bench_util import probe_device

    probe_device()

    mesh_shape = json.loads(args.mesh_shape) if args.mesh_shape else None
    record = build_record(
        n_envs=args.n_envs, horizon=args.horizon, iters=args.iters,
        mesh_shape=mesh_shape, measure_split=not args.quick,
        profile_dir=args.profile,
    )
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
