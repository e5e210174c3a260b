"""Benchmark helpers: XLA-counted FLOPs and model FLOPs utilization.

MFU here is defined against the XLA cost model of the FULL compiled
train step (policy matmuls + optimizer + env arithmetic — the env's
elementwise math is a rounding error next to the policy GEMMs), divided
by the chip's public peak dense-bf16 throughput.  That makes it an
end-to-end hardware-utilization number for the fused
rollout+update program, reproducible from the compiled executable
alone (no hand-counted FLOP formulas to drift out of date).
"""
from __future__ import annotations

import time
from typing import Any, Optional


def probe_device() -> None:
    """The first device op of a benchmark: a tiny matmul, synchronised.
    Whatever JAX raises — no accelerator found, a failed first op — is
    raised as it is, so the run exits non-zero with the error; nothing
    is printed in its place."""
    import jax.numpy as jnp

    (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()


# timed iterations by default: short runs are dominated by the first
# dispatches' host overhead
DEFAULT_BENCH_ITERS = 20


def compile_train_step(trainer: Any, state: Any, k: Optional[int] = None):
    """AOT-compile a trainer's donated step program (``k=None``) or its
    K-step ``train_many`` superstep for ``state``: ``(compiled,
    flops_or_None)``.  The executable is what the benchmarks and
    ``chip_smoke.py`` run and read (``as_text()``, cost analysis), so
    the program is compiled once.  It is also registered as the newest
    step program (``telemetry/scopes.py``), so that a trace of it can be
    joined to the layers its instructions were written in."""
    from gymfx_tpu.telemetry import scopes

    if k is None:
        compiled, flops = compile_with_flops(trainer._train_step, state)
    else:
        compiled, flops = compile_with_flops(trainer._train_many, state, int(k))
    scopes.register_step(compiled)
    return compiled, flops


def measure_train_step(trainer: Any, state: Any, iters: int):
    """One shared timing harness for every benchmark: AOT-compile once
    (cost analysis + execution off the same executable), warmup, timed
    loop.  Returns ``(seconds, flops_per_iter, final_state, step)`` —
    ``step`` is the compiled callable so callers (e.g. the profiler
    capture) never trigger a second compilation of the same program."""
    import jax

    step, flops = compile_train_step(trainer, state)
    state, _ = step(state)  # warmup
    jax.block_until_ready(state)  # whole pytree: works for every trainer
    t0 = time.perf_counter()
    for _ in range(iters):
        state, _metrics = step(state)
    jax.block_until_ready(state)
    return time.perf_counter() - t0, flops, state, step

def measure_train_many(trainer: Any, state: Any, dispatches: int, k: int):
    """Superstep twin of :func:`measure_train_step`: times ``dispatches``
    invocations of the compiled K-step ``train_many`` program (one
    donated lax.scan dispatch per K train steps).  Returns ``(seconds,
    flops_per_dispatch, final_state, step)`` — divide seconds by
    ``dispatches * k`` for per-train-step time."""
    import jax

    # static k is baked into the executable
    step, flops = compile_train_step(trainer, state, k)
    state, _ = step(state)  # warmup
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(dispatches):
        state, _metrics = step(state)
    jax.block_until_ready(state)
    return time.perf_counter() - t0, flops, state, step


def measure_phase_split(trainer: Any, state: Any, iters: int):
    """Phase-attributed twin of :func:`measure_train_step`: times the
    rollout and update halves of the train step as two donated-carry
    sub-programs compiled off the same phase methods the fused step is
    composed from (``_rollout_phase`` / ``_update_phase``), so the split
    is measured on real executables rather than inferred.

    The sum slightly overstates the fused step (two dispatches, a
    host sync between phases, and no cross-phase fusion), so callers
    should report the *fraction* against the fused per-step time.
    Returns ``(rollout_seconds, update_seconds, final_state,
    update_flops)`` — ``update_flops`` is the XLA cost-model FLOPs of
    the compiled update phase (the GEMM chain), None where the backend
    hides cost analysis — or ``None`` when the trainer has no phase
    methods.
    """
    import jax

    if not (hasattr(trainer, "_rollout_phase")
            and hasattr(trainer, "_update_phase")):
        return None

    r_jit = jax.jit(trainer._rollout_phase, donate_argnums=0)
    u_jit = jax.jit(trainer._update_phase, donate_argnums=(0, 1))
    r_step, _ = compile_with_flops(r_jit, state)
    inter, rollout_out = r_step(state)
    u_step, u_flops = compile_with_flops(u_jit, inter, rollout_out)
    state, _ = u_step(inter, rollout_out)  # warmup both phases
    jax.block_until_ready(state)

    rollout_s = update_s = 0.0
    for _ in range(iters):
        t0 = time.perf_counter()
        inter, rollout_out = r_step(state)
        jax.block_until_ready((inter, rollout_out))
        t1 = time.perf_counter()
        state, _metrics = u_step(inter, rollout_out)
        jax.block_until_ready(state)
        update_s += time.perf_counter() - t1
        rollout_s += t1 - t0
    return rollout_s, update_s, state, u_flops


def stamp_comparability(record: dict, device: Any = None) -> dict:
    """Stamp the comparability triple the bench sentinel gates on:
    ``platform`` / ``device_kind`` (where the row was measured) and
    ``comparable`` (False on CPU proxies unless the caller already
    decided).  Shared by ``emit_bench_record`` and the record builders
    that print their own contract line (tools/multichip_bench.py)."""
    if device is None:
        import jax

        device = jax.local_devices()[0]
    record.setdefault("platform", str(device.platform))
    record.setdefault("device_kind", str(device.device_kind))
    # CPU rows are functional proxies, never trajectory anchors; any
    # explicit caller verdict wins over the platform heuristic
    record.setdefault("comparable", record["platform"] != "cpu")
    return record


def emit_bench_record(
    record: dict,
    *,
    analytic_flops: Optional[float] = None,
    step_time_s: Optional[float] = None,
    device: Any = None,
) -> dict:
    """ONE row-construction path for every benchmark emitter (bench.py
    ppo/lob/scengen mains, tools/tpu_bench.py sweep rows): append the
    telemetry/mfu.py analytic-MFU slice — analytic_flops_per_step /
    hw_flops_peak / mfu_analytic / device_memory_bytes, every key
    always present, null where the backend or workload cannot say
    (CPU peak FLOPs; integer workloads with no FLOP model) — plus the
    comparability stamp the bench sentinel gates on: ``platform`` /
    ``device_kind`` (where the row was measured) and ``comparable``
    (False on CPU proxies unless the caller already decided), then
    print the record as the single JSON contract line and return it.
    When a run ledger is active the row is also ledgered."""
    import json

    from gymfx_tpu.telemetry.mfu import mfu_report

    record.update(mfu_report(analytic_flops, step_time_s, device))
    stamp_comparability(record, device=device)
    from gymfx_tpu.telemetry.ledger import get_active_ledger

    ledger = get_active_ledger()
    if ledger is not None:
        ledger.record(
            "bench_row", metric=record.get("metric"),
            value=record.get("value"),
            comparable=record.get("comparable"),
            platform=record.get("platform"),
        )
    print(json.dumps(record), flush=True)
    return record


# Peak dense-bf16 FLOPs/sec per chip, keyed by the EXACT ``device_kind``
# string JAX reports (``jax.devices()[0].device_kind``; chip_smoke.py's
# ``device`` phase prints it).  Source: Google Cloud documentation, "TPU
# v5e" system architecture — 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s
# per chip.  A kind is added here together with the run that printed it.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def device_peak_flops(device: Any) -> Optional[float]:
    """Peak dense-bf16 FLOPs/sec of ``device``.  A CPU is a functional
    proxy with no peak (None: its rows are ``comparable: false`` and
    carry no utilization); any other device missing from the table is
    an error, not a default — a measuring path must know its roofline."""
    if getattr(device, "platform", None) == "cpu":
        return None
    kind = getattr(device, "device_kind", None)
    if kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"device_kind {kind!r} has no entry in "
            "bench_util.PEAK_BF16_FLOPS; add it with its published peak "
            "and source before measuring on it"
        )
    return PEAK_BF16_FLOPS[kind]


def compile_with_flops(jitted_fn: Any, *args: Any):
    """AOT-compile ``jitted_fn`` for ``args`` ONCE and read the XLA cost
    analysis off the same executable: ``(compiled, flops_or_None)``.
    Benchmarks execute the returned executable directly, so the program
    is never compiled a second time through the jit dispatch cache.  A
    compile error is raised as it is; ``flops`` is None only where the
    backend's cost analysis has no count."""
    compiled = jitted_fn.lower(*args).compile()
    analysis = compiled.cost_analysis()
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    raw = analysis.get("flops") if analysis else None
    return compiled, float(raw) if raw and raw > 0 else None


def mfu(flops_per_iter: Optional[float], iters: int, seconds: float,
        device: Any) -> Optional[float]:
    """Achieved / peak FLOPs fraction, or None when either side is
    unknown."""
    peak = device_peak_flops(device)
    if not (flops_per_iter and peak and seconds > 0):
        return None
    return (flops_per_iter * iters / seconds) / peak
