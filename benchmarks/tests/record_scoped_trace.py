#!/usr/bin/env python3
"""Record the small chip trace of a REAL train step, with the step's scope
map beside it, that test_scope_times.py reads (run ON THE CHIP, by hand,
when the profiler's format or the scope vocabulary changes):

    python benchmarks/tests/record_scoped_trace.py chiprun_out/scoped_trace

A tiny PPO trainer (``transformer_ring`` at window 192, the shortest the
fused attention takes; 128 envs x 2 steps, 2 minibatches, both env-dynamics
kernels on), compiled by ``bench_util.compile_train_step`` as the train
runner compiles the measured program, two steps inside ``bench.window``.
The trace then holds every layer of the vocabulary, forward and backward,
the four named kernels and the scans' ``while`` events.  Writes, into the
directory given:

    scoped.xplane.pb.gz     the profiler's trace, gzipped
    scoped.scope_map.json   {instruction name: [scope path, direction]}
    scoped.expected.json    reduce_trace.reduce's busy_s and window_s, the
                            steps, and scope_times.note's table (to be
                            looked over before it is committed)

``--rehearse`` runs the same on the CPU (kernels interpreted, window 16) to
rehearse the script; such files are never committed.
"""
import gzip
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(BENCH), str(BENCH.parent)]

STEPS = 2


def main(out_dir: str, rehearse: bool) -> int:
    import harness

    devices, _cache = harness.open_devices(1, rehearse)
    if devices is None:
        return 2
    import jax

    import reduce_trace
    import scope_times
    from gymfx_tpu.bench_util import compile_train_step
    from gymfx_tpu.config import DEFAULT_VALUES
    from gymfx_tpu.core.runtime import Environment
    from gymfx_tpu.telemetry import scopes
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from
    from tape import ensure_tape

    config = dict(DEFAULT_VALUES)
    config.update(
        input_data_file=str(ensure_tape(
            {"generator": "m1_quarter", "bars": 3000, "seed": 20260701})),
        policy="transformer_ring", policy_dtype="bfloat16",
        window_size=16 if rehearse else 192, num_envs=128, ppo_horizon=2,
        ppo_epochs=1, ppo_minibatches=2, ppo_minibatch_scheme="env_permute",
        rollout_env_kernel="interpret" if rehearse else "on",
    )
    trainer = PPOTrainer(Environment(config), ppo_config_from(config))
    state = trainer.init_state(0)
    step, _flops = compile_train_step(trainer, state)
    state, _ = step(state)
    jax.block_until_ready(state)

    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    result = {}
    with harness.traced_window("scoped", result, root=out / "raw"):
        for _ in range(STEPS):
            with jax.profiler.TraceAnnotation("bench.train_step"):
                state, metrics = step(state)
        with jax.profiler.TraceAnnotation("bench.block_until_ready"):
            jax.block_until_ready(state)
    xplane = Path(result.pop("xplane"))
    scope_map = scopes.last_step_scope_map()
    table = scope_times.build(scope_times.op_seconds(xplane), scope_map, STEPS)
    with open(xplane, "rb") as raw, gzip.open(out / "scoped.xplane.pb.gz", "wb") as packed:
        shutil.copyfileobj(raw, packed)
    shutil.rmtree(out / "raw")
    (out / "scoped.scope_map.json").write_text(json.dumps(scope_map, indent=0) + "\n")
    expected = {"busy_s": result["busy_s"], "window_s": result["window_s"],
                "kernel_s": result["kernel_s"], "steps": STEPS,
                "loss": float(metrics["loss"]), "scope_ms": scope_times.note(table)}
    (out / "scoped.expected.json").write_text(json.dumps(expected, indent=1) + "\n")
    print(json.dumps(expected))
    print("bytes", {p.name: p.stat().st_size for p in sorted(out.iterdir())})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], "--rehearse" in sys.argv))
