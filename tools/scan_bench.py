#!/usr/bin/env python3
"""Policy-free vectorized env-scan throughput ->
examples/results/tpu_scan_bench.json.

Measures the raw engine (every env advances through the FULL step:
pending fills, brackets, strategy, mark-to-market, streaming obs) with
no policy attached, through the same chunked vmapped path the CLI's
batch evaluation uses (app/main.py `chunk_call`).  The PPO headline in
bench.py adds the policy forward + update on top of this.

Usage: python tools/scan_bench.py [--quick] [--output PATH]
"""
from __future__ import annotations

import argparse
import datetime
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from gymfx_tpu.compile_cache import enable_compile_cache

enable_compile_cache()

CHUNK = 64
CHUNKS = 6
REPS = 3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny widths (CI smoke; artifact not written)")
    ap.add_argument("--output", default="examples/results/tpu_scan_bench.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from gymfx_tpu.config import DEFAULT_VALUES
    from gymfx_tpu.core import env as env_core
    from gymfx_tpu.core.rollout import _rollout_chunk, random_driver
    from gymfx_tpu.core.runtime import Environment

    config = dict(DEFAULT_VALUES,
                  input_data_file="examples/data/eurusd_sample.csv",
                  window_size=32)
    env = Environment(config)
    driver = random_driver()
    widths = (256,) if args.quick else (8192, 32768)

    rows = []
    for n_envs in widths:
        keys = jax.random.split(jax.random.PRNGKey(0), n_envs)
        vreset = jax.jit(jax.vmap(
            lambda _i: env_core.reset(env.cfg, env.params, env.data),
            in_axes=0,
        ))
        states_b, obs_b = vreset(jnp.arange(n_envs))

        def chunk_call(states_b, obs_b, keys_b, offset):
            f = jax.vmap(
                lambda st, ob, k: _rollout_chunk(
                    env.cfg, env.params, env.data, driver, CHUNK,
                    st, ob, k, (), jnp.asarray(offset, jnp.int32), False,
                )
            )
            return f(states_b, obs_b, keys_b)

        states_b, obs_b, keys, _dc, _ = chunk_call(states_b, obs_b, keys, 0)
        jax.block_until_ready(states_b.t)  # compile + warmup
        best = 0.0
        for _rep in range(REPS):
            t0 = time.perf_counter()
            sb, ob, kk = states_b, obs_b, keys
            off = CHUNK
            for _c in range(CHUNKS):
                sb, ob, kk, _dc, _ = chunk_call(sb, ob, kk, off)
                off += CHUNK
            jax.block_until_ready(sb.t)
            best = max(best, n_envs * CHUNK * CHUNKS / (time.perf_counter() - t0))
        rows.append({"n_envs": n_envs,
                     "env_steps_per_sec_per_chip": round(best, 1)})
        print(json.dumps(rows[-1]), flush=True)

    artifact = {
        "schema": "tpu_scan_bench.v2",
        "date_utc": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"
        ),
        "device": str(getattr(jax.devices()[0], "device_kind", "?")),
        "workload": "vmapped policy-free env scan through the CLI "
                    "batch-eval path (_rollout_chunk under jax.vmap, "
                    f"{CHUNK}-step chunks, random driver, collect=False), "
                    "EUR/USD 1-min bars, window 32; best of "
                    f"{REPS} reps x {CHUNKS} chunks",
        "methodology_note": "measures the vectorized engine: every env "
                            "advances through the full step (pending "
                            "fills, brackets, strategy, mark, streaming "
                            "obs). The PPO headline in bench.py adds "
                            "policy forward + PPO update.",
        "rows": rows,
    }
    if not args.quick:
        out = Path(args.output)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(artifact, indent=1))
        print(f"wrote {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
