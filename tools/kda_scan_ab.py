#!/usr/bin/env python3
"""Chip A/B of the gated-delta-rule scan (``ops/kda_chunk_scan.py``): time of
the forward and of forward + backward at one shape, one JSON line a variant,
then the variants' largest differences.  ``python3 tools/kda_scan_ab.py [B W H D]``
(default 4 1024 32 128, bfloat16 operands, float32 decay).  ``VARIANTS`` names
the functions of that module to time: ``kda_chunk_scan``, which ships with a
backward pass of its own (PR 34), and ``scan_forward``, the same forward left
to JAX's derivative (every window's stacked residuals at once: the parent's
backward pass without the ``jax.checkpoint`` that walked each window again),
or whatever else is tried against it (PR 33 timed a Mosaic kernel pair here and
dropped it).  Numbers from a CPU are no device numbers."""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

VARIANTS = ("kda_chunk_scan", "scan_forward")


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    from gymfx_tpu.ops import kda_chunk_scan as ops

    b, w, h, d = (int(x) for x in argv[1:5]) if len(argv) >= 5 else (4, 1024, 32, 128)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = (unit(jax.random.normal(keys[0], (b, w, h, d))) * d ** -0.5).astype(jnp.bfloat16)
    k = unit(jax.random.normal(keys[1], (b, w, h, d))).astype(jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, w, h, d)).astype(jnp.bfloat16)
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(keys[3], (b, w, h, d)) - 3.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, w, h)))
    args = (q, k, v, g, beta)
    variants = {name: getattr(ops, name) for name in VARIANTS}
    outs = {}
    for name, scan in variants.items():
        forward = jax.jit(scan)
        both = jax.jit(jax.grad(
            lambda *a: jnp.sum(scan(*a).astype(jnp.float32) ** 2), argnums=(0, 1, 2, 3, 4)))
        row = {"variant": name, "shape": [b, w, h, d], "device": jax.devices()[0].device_kind}
        for label, fn in (("forward_ms", forward), ("forward_backward_ms", both)):
            t = time.perf_counter()
            out = jax.block_until_ready(fn(*args))
            row[label.replace("_ms", "_compile_s")] = time.perf_counter() - t
            times = []
            for _ in range(10):
                t = time.perf_counter()
                jax.block_until_ready(fn(*args))
                times.append(time.perf_counter() - t)
            row[label] = 1e3 * sorted(times)[len(times) // 2]
            outs[name, label] = out
        print(json.dumps(row), flush=True)
    if len(variants) == 2:
        a, c = (outs[n, "forward_ms"].astype(jnp.float32) for n in variants)
        ga, gc = (outs[n, "forward_backward_ms"] for n in variants)
        print(json.dumps({
            "forward_max_abs_diff": float(jnp.abs(a - c).max()), "forward_max_abs": float(jnp.abs(a).max()),
            "grad_rel_diff": [float(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)).max()
                                    / jnp.abs(x.astype(jnp.float32)).max()) for x, y in zip(ga, gc)]}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
