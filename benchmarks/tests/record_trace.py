#!/usr/bin/env python3
"""Record the small chip trace that test_reduce_trace.py reads (run ON THE
CHIP, by hand, when the profiler's format changes):

    python benchmarks/tests/record_trace.py chiprun_out/small_trace

A scan of four steps, each a tiny Mosaic kernel and a matmul, dispatched
three times inside ``bench.window`` with a host sleep between them, so the
trace holds everything the reduction reads: a ``while`` enclosing its body's
ops, ``tpu_custom_call`` events, idle gaps under named host spans.  Writes
``small.xplane.pb`` and ``small.expected.json`` (the reduction's own result,
to be looked over before it is committed) into the directory given.
"""
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(BENCH), str(BENCH.parent)]


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    import harness
    import reduce_trace

    if jax.devices()[0].platform != "tpu":
        print("record_trace.py: no TPU", file=sys.stderr)
        return 2

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    kernel = pl.pallas_call(
        double, out_shape=jax.ShapeDtypeStruct((256, 128), jnp.float32))
    w = jnp.eye(128, dtype=jnp.float32) * 0.5

    @jax.jit
    def step(x):
        def body(c, _):
            return kernel(c) @ w, None
        return jax.lax.scan(body, x, None, length=4)[0]

    x = jnp.ones((256, 128), jnp.float32)
    step(x).block_until_ready()
    result = {}
    with harness.traced_window("small", result, root=Path(out_dir) / "raw"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.train_step"):
                x = step(x)
            with jax.profiler.TraceAnnotation("bench.fetch_loss"):
                x.block_until_ready()
                time.sleep(0.002)
    out = Path(out_dir)
    shutil.copy(result.pop("xplane"), out / "small.xplane.pb")
    shutil.rmtree(out / "raw")
    (out / "small.expected.json").write_text(json.dumps(result, indent=1) + "\n")
    print(json.dumps(result))
    reduce_trace.dump(out / "small.xplane.pb", top=8)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
