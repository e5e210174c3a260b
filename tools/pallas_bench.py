#!/usr/bin/env python3
"""Pallas window-zscore kernel: exactness + speedup evidence ->
examples/results/pallas_kernel_bench.json.

Benchmarks the fused gather+normalize+clip TPU kernel
(gymfx_tpu/ops/window_zscore.py) against its plain-XLA reference on the
local accelerator and records max|err| (must be 0: same arithmetic,
fused scheduling) plus the per-call wall times.

Usage: python tools/pallas_bench.py [--quick] [--output PATH]
"""
from __future__ import annotations

import argparse
import datetime
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from gymfx_tpu.bench_util import DEFAULT_BENCH_ITERS
from gymfx_tpu.compile_cache import enable_compile_cache

enable_compile_cache()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny shapes (CI smoke; artifact not written)")
    ap.add_argument("--output",
                    default="examples/results/pallas_kernel_bench.json")
    ap.add_argument("--iters", type=int, default=DEFAULT_BENCH_ITERS)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from gymfx_tpu.ops.dispatch import on_tpu
    from gymfx_tpu.ops.window_zscore import (
        batched_scaled_windows,
        reference_scaled_windows,
    )

    if args.quick:
        n, w, f, b = 256, 16, 8, 64
    else:
        n, w, f, b = 4096, 64, 32, 2048
    rng = np.random.default_rng(0)
    padded = jnp.asarray(rng.normal(size=(n + w, f)), jnp.float32)
    mean = jnp.asarray(rng.normal(size=(n + 1, f)), jnp.float32)
    std = jnp.asarray(rng.uniform(0.5, 2.0, size=(n + 1, f)), jnp.float32)
    neutral = jnp.zeros((n + 1,), bool)
    steps = jnp.asarray(rng.integers(0, n, b), jnp.int32)

    # jit BOTH sides: the comparison is compiled-kernel vs compiled-XLA,
    # not compiled vs op-by-op trace overhead
    import functools

    ref_jit = jax.jit(functools.partial(
        reference_scaled_windows, window=w, clip=10.0
    ))
    out = batched_scaled_windows(padded, mean, std, neutral, steps, window=w)
    ref = ref_jit(padded, mean, std, neutral, steps)
    err = float(jnp.max(jnp.abs(out - ref)))

    def timed(fn):
        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for _ in range(args.iters):
            r = fn()
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / args.iters

    pallas_s = timed(lambda: batched_scaled_windows(
        padded, mean, std, neutral, steps, window=w))
    xla_s = timed(lambda: ref_jit(padded, mean, std, neutral, steps))

    device = jax.devices()[0]
    artifact = {
        "schema": "pallas_kernel_bench.v1",
        "date_utc": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"
        ),
        "device": str(getattr(device, "device_kind", device.platform)),
        "platform": device.platform,
        "kernel": "ops/window_zscore.py batched_scaled_windows (fused HBM "
                  "window DMA + leakage-safe z-score + clip, "
                  "PrefetchScalarGridSpec)",
        "workload": f"B={b} windows of {w} rows x {f} features from "
                    f"a {n}-bar history, per-step scaler moments",
        "max_abs_err_vs_xla_reference": err,
        "pallas_seconds_per_call": round(pallas_s, 6),
        "xla_reference_seconds_per_call": round(xla_s, 6),
        "speedup": round(xla_s / pallas_s, 2) if pallas_s > 0 else None,
        "interpret_mode": not on_tpu(),
    }
    print(json.dumps({k: artifact[k] for k in (
        "max_abs_err_vs_xla_reference", "pallas_seconds_per_call",
        "xla_reference_seconds_per_call", "speedup", "interpret_mode",
    )}), flush=True)
    assert err == 0.0, f"kernel diverged from reference: {err}"
    if not args.quick:
        out_path = Path(args.output)
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(artifact, indent=1))
        print(f"wrote {out_path}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
