#!/usr/bin/env python3
"""Serving benchmark — prints ONE JSON line.

Workload: the serving stack (gymfx_tpu/serve/) on the north-star MLP
policy — the AOT-compiled bucket ladder fed by the micro-batching
scheduler.  Three numbers are measured off the same warm engine:

  * sequential baseline: the PRE-ENGINE live path — one jitted
    batch-of-1 ``apply_seq`` dispatch plus a host argmax per decision;
  * bucketed throughput (the headline): a closed loop of full-batch
    ``decide_batch`` dispatches — decisions/sec/chip;
  * request latency: concurrent client threads submitting single
    observations through the MicroBatcher; p50/p99 wall latency comes
    from its per-request records (enqueue -> resolve).

A fourth phase is a scripted OVERLOAD scenario (docs/serving.md): the
engine is wrapped in a seeded FlakyEngine (slow dispatches), a second
admission-controlled batcher (small queue, 50ms deadlines) takes
burst-shaped arrivals, and the line reports the serving SLO trio —
``shed_rate``, ``deadline_miss_rate`` and the overload ``p99_ms``.
``--fault_profile`` overrides the scripted scenario (grammar in
gymfx_tpu/resilience/faults.py).

Usage: python bench_infer.py [--policy P] [--batch N] [--iters K]
                             [--clients C] [--wait_ms W] [--quick]
                             [--fault_profile SPEC]
"""
import argparse
import json
import sys

from gymfx_tpu.compile_cache import enable_compile_cache

enable_compile_cache()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="mlp")
    ap.add_argument("--batch", type=int, default=1024,
                    help="closed-loop dispatch batch (throughput phase)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--clients", type=int, default=64,
                    help="concurrent client threads (latency phase)")
    ap.add_argument("--requests", type=int, default=50,
                    help="requests per client thread")
    ap.add_argument("--wait_ms", type=float, default=2.0,
                    help="micro-batcher coalescing window")
    ap.add_argument("--batch_mode", default="auto",
                    choices=("auto", "exact", "matmul"))
    ap.add_argument("--fault_profile", default="",
                    help="overload-phase fault profile (default: the "
                         "scripted burst-overload scenario)")
    ap.add_argument("--session_slots", type=int, default=0,
                    help="A/B the device-resident slot-cache serve path "
                         "against host-carry at the same batch size "
                         "(recurrent policies only; 0 = off)")
    ap.add_argument("--quick", action="store_true", help="small shapes (CI)")
    args = ap.parse_args()
    buckets = None
    if args.quick:
        args.iters = 3
        args.clients, args.requests = 8, 20
        buckets = (1, 8, args.batch)  # lean ladder: CI pays 3 compiles
        if args.batch_mode == "auto":
            # the quick line is a THROUGHPUT smoke: use the GEMM mode
            # everywhere (auto would pick the bit-exact sequential-row
            # mode on CPU; parity is the test suite's job, not CI's)
            args.batch_mode = "matmul"

    from gymfx_tpu.bench_util import probe_device

    probe_device()

    import time

    import numpy as np
    import jax

    from gymfx_tpu.config import DEFAULT_VALUES
    from gymfx_tpu.serve import MicroBatcher, engine_from_config

    config = dict(DEFAULT_VALUES)
    config.update(
        input_data_file="examples/data/eurusd_sample.csv",
        policy=args.policy,
        serve_batch_mode=args.batch_mode,
        window_size=32,
    )
    if buckets is not None:
        config["serve_buckets"] = list(buckets)
    config["serve_max_batch_wait_ms"] = args.wait_ms

    t0 = time.perf_counter()
    bundle = engine_from_config(config)  # warm: every bucket compiles here
    engine = bundle.engine
    boot_s = time.perf_counter() - t0

    # request stream: the env's reset observation row plus bounded noise
    # (row values never change the FLOPs, only keep caches honest)
    base = np.asarray(bundle.encode(bundle.reset_obs), engine.obs_dtype)
    rng = np.random.default_rng(0)
    rows = base[None] + 0.01 * rng.standard_normal(
        (args.batch, *engine.obs_shape)
    ).astype(engine.obs_dtype)
    carries = (
        engine.initial_carry_batch(args.batch) if engine.recurrent else None
    )

    # --- sequential baseline: the pre-engine live path ------------------
    # one jitted batch-of-1 dispatch + host argmax per decision — what
    # live/oanda.py paid per tick before the serving stack existed
    import jax.numpy as jnp

    seq_n = min(args.batch, 64 if args.quick else 256)
    carry1 = bundle.engine.policy.initial_carry(())
    naive = jax.jit(engine.policy.apply_seq)
    out0 = naive(engine.params, jnp.asarray(rows[0]), carry1)
    jax.block_until_ready(out0)
    t0 = time.perf_counter()
    for i in range(seq_n):
        out, _value, _c = naive(engine.params, jnp.asarray(rows[i]), carry1)
        head = out[0] if engine.continuous else out
        int(np.argmax(np.asarray(head)))
    seq_per_sec = seq_n / (time.perf_counter() - t0)

    # --- bucketed closed-loop throughput (headline) ---------------------
    engine.decide_batch(rows, carries)  # touch once before timing
    t0 = time.perf_counter()
    for _ in range(args.iters):
        engine.decide_batch(rows, carries)
    batched_per_sec = args.batch * args.iters / (time.perf_counter() - t0)

    # --- device-resident slot cache A/B (docs/serving.md) ---------------
    # same engine, same rows, same batch width: host-carry loop (carry
    # crosses the host boundary both ways every dispatch) vs slot loop
    # (carry lives in device slots; only the one-dispatch-late mirror is
    # fetched).  Keys are ALWAYS emitted — null when the mode is off or
    # the policy has no carry to cache.
    slot_keys = {
        "session_slots": None,
        "slot_decisions_per_sec": None,
        "carry_transfer_bytes_per_decision": None,
        "carry_transfer_bytes_per_decision_host": None,
        "speedup_vs_host_carry": None,
    }
    if args.session_slots > 0 and engine.recurrent:
        n_slot = min(args.batch, int(engine.buckets[-1]), args.session_slots)
        slot_rows = rows[:n_slot]
        sessions = [f"bench-{i}" for i in range(n_slot)]
        engine.enable_slots(args.session_slots)
        # host-carry side at the SAME width (the headline above may run
        # a different batch): thread the returned carry like a real
        # session stream so every dispatch pays the round trip
        hc = engine.initial_carry_batch(n_slot)
        d = engine.decide_batch(slot_rows, hc)  # touch once before timing
        t0 = time.perf_counter()
        hc = d.carry
        for _ in range(args.iters):
            hc = engine.decide_batch(slot_rows, hc).carry
        host_per_sec = n_slot * args.iters / (time.perf_counter() - t0)
        # slot side: first call assigns + compiles nothing new (warmup
        # built the ladder), later calls are pure gather->fwd->scatter
        engine.decide_batch_slots(slot_rows, sessions)
        dec0 = engine.slot_decisions
        bytes0 = engine.mirror_fetch_bytes
        t0 = time.perf_counter()
        for _ in range(args.iters):
            engine.decide_batch_slots(slot_rows, sessions)
        slot_per_sec = n_slot * args.iters / (time.perf_counter() - t0)
        slot_decs = max(1, engine.slot_decisions - dec0)
        mirror_bytes = engine.mirror_fetch_bytes - bytes0
        # analytic host-path cost: the full carry pytree crosses the
        # boundary down AND up once per decision
        carry_bytes = sum(
            np.asarray(leaf).nbytes
            for leaf in jax.tree_util.tree_leaves(engine.initial_carry())
        )
        slot_keys = {
            "session_slots": args.session_slots,
            "slot_decisions_per_sec": round(slot_per_sec, 1),
            "carry_transfer_bytes_per_decision": round(
                mirror_bytes / slot_decs, 1
            ),
            "carry_transfer_bytes_per_decision_host": float(2 * carry_bytes),
            "speedup_vs_host_carry": round(
                slot_per_sec / max(host_per_sec, 1e-9), 2
            ),
        }

    # --- micro-batched request latency ----------------------------------
    import threading

    batcher = MicroBatcher(engine, max_batch_wait_ms=args.wait_ms)

    def client(cid: int) -> None:
        carry = engine.initial_carry() if engine.recurrent else None
        for j in range(args.requests):
            fut = batcher.submit(rows[(cid + j) % args.batch], carry)
            d = fut.result()
            if engine.recurrent:
                carry = d.carry

    threads = [
        threading.Thread(target=client, args=(c,)) for c in range(args.clients)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    lat_wall = time.perf_counter() - t0
    records = batcher.records
    batcher.close()
    lat_ms = np.asarray([r.latency_s for r in records]) * 1e3
    coalesce = (
        batcher.coalesced_total / batcher.dispatches
        if batcher.dispatches
        else 0.0
    )

    # --- scripted overload scenario (chaos phase) -----------------------
    # a second, admission-controlled batcher over a FlakyEngine: slow
    # 80ms dispatches, max 8-wide batches, a 16-deep queue and 50ms
    # deadlines under 4 bursts of 32 simultaneous arrivals — structural
    # overload, so the shed/deadline machinery measurably engages while
    # the phases above keep exercising the untouched fast path
    from gymfx_tpu.resilience import (
        flaky_engine_from_profile,
        parse_fault_profile,
    )
    from gymfx_tpu.serve import DeadlineExceeded, ShedError

    profile_spec = args.fault_profile or (
        "serve=" + "+".join(["slow:80"] * 16) + ";burst=32x4;seed=0"
    )
    profile = parse_fault_profile(profile_spec)
    burst = profile.get("burst") or {"size": 32, "rounds": 4}
    flaky = flaky_engine_from_profile(engine, profile)
    # the chaos batcher runs INSTRUMENTED: every shed/deadline/latency
    # event lands in a metrics registry exposed over a live (ephemeral-
    # port) /metrics endpoint, and the line reports what one Prometheus
    # scrape of the burst saw — proving the serving telemetry end to end
    from gymfx_tpu.telemetry import MetricsRegistry, SLOWindow
    from gymfx_tpu.telemetry.http import TelemetryServer, scrape
    from gymfx_tpu.telemetry.instruments import ServeInstruments

    registry = MetricsRegistry()
    instr = ServeInstruments(
        registry, slo=SLOWindow(window_s=60.0), name="overload"
    )
    over = MicroBatcher(
        flaky,
        max_batch_wait_ms=1.0,
        max_batch=8,
        max_queue=16,
        shed_policy="reject",
        default_deadline_ms=50.0,
        instruments=instr,
    )
    metrics_server = TelemetryServer(registry, health_fn=over.health, port=0)
    outcomes = {"served": 0, "shed": 0, "deadline_miss": 0, "failed": 0}
    outcome_lock = threading.Lock()

    def burst_client(i: int) -> None:
        carry = engine.initial_carry() if engine.recurrent else None
        try:
            fut = over.submit(rows[i % args.batch], carry)
            fut.result(timeout=30.0)
            kind = "served"
        except ShedError:
            kind = "shed"
        except DeadlineExceeded:
            kind = "deadline_miss"
        except Exception:
            kind = "failed"
        with outcome_lock:
            outcomes[kind] += 1

    t0 = time.perf_counter()
    for r in range(int(burst["rounds"])):
        wave = [
            threading.Thread(
                target=burst_client, args=(r * int(burst["size"]) + i,)
            )
            for i in range(int(burst["size"]))
        ]
        for t in wave:
            t.start()
        for t in wave:
            t.join()
    over_wall = time.perf_counter() - t0
    over_records = over.records
    over_health = over.health()
    # one real HTTP scrape while the registry is hot: the exposition the
    # bench reports is what an operator's Prometheus would have pulled
    exposition = scrape(metrics_server.url + "/metrics")
    scraped_served = scraped_shed = None
    for line in exposition.splitlines():
        if line.startswith("gymfx_serve_requests_total") and 'outcome="served"' in line:
            scraped_served = float(line.rsplit(" ", 1)[1])
        if line.startswith("gymfx_serve_requests_total") and 'outcome="shed"' in line:
            scraped_shed = float(line.rsplit(" ", 1)[1])
    slo_rates = instr.slo.rates()
    metrics_server.close()
    over.close()
    submitted = int(burst["size"]) * int(burst["rounds"])
    over_lat_ms = np.asarray(
        [r.latency_s for r in over_records] or [0.0]
    ) * 1e3
    shed_rate = outcomes["shed"] / submitted
    deadline_miss_rate = outcomes["deadline_miss"] / submitted

    chips = max(1, jax.local_device_count())
    dev = jax.local_devices()[0]
    platform = str(getattr(dev, "platform", "unknown"))
    device_kind = str(getattr(dev, "device_kind", platform))
    print(
        json.dumps(
            {
                "metric": "serve_decisions_per_sec_per_chip",
                "value": round(batched_per_sec / chips, 1),
                "unit": f"decisions/sec/chip ({args.policy} policy, "
                        f"{engine.batch_mode} batching, bucket ladder "
                        f"{list(engine.buckets)})",
                "decisions_per_sec_per_chip": round(batched_per_sec / chips, 1),
                "sequential_per_sec": round(seq_per_sec, 1),
                "speedup_vs_sequential": round(
                    batched_per_sec / max(seq_per_sec, 1e-9), 2
                ),
                "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
                "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
                "requests": len(records),
                "mean_coalesced_per_dispatch": round(coalesce, 1),
                "late_compiles": engine.late_compiles,
                "boot_compile_s": round(boot_s, 2),
                # device-resident slot-cache A/B (null when off or the
                # policy carries no recurrent state)
                **slot_keys,
                "latency_throughput_per_sec": round(
                    len(records) / lat_wall, 1
                ),
                # serving SLO trio under the scripted overload scenario
                "shed_rate": round(shed_rate, 4),
                "deadline_miss_rate": round(deadline_miss_rate, 4),
                # comparability stamp the bench sentinel gates on
                # (tools/bench_sentinel.py): CPU rows are proxies
                "platform": platform,
                "device_kind": device_kind,
                "comparable": platform not in ("cpu", "unknown"),
                "overload": {
                    "fault_profile": profile_spec,
                    "submitted": submitted,
                    "served": outcomes["served"],
                    "shed": outcomes["shed"],
                    "deadline_missed": outcomes["deadline_miss"],
                    "failed": outcomes["failed"],
                    "p99_ms": round(
                        float(np.percentile(over_lat_ms, 99)), 3
                    ),
                    "wall_s": round(over_wall, 3),
                    "shed_count": over_health["shed_count"],
                    "deadline_miss_count": over_health[
                        "deadline_miss_count"
                    ],
                    "dispatch_failures": over_health["dispatch_failures"],
                },
                # live-scrape proof: what one /metrics pull over the
                # ephemeral telemetry endpoint reported for the burst,
                # plus the rolling-window SLO gauges' view
                "telemetry": {
                    "scrape_bytes": len(exposition),
                    "scraped_served_total": scraped_served,
                    "scraped_shed_total": scraped_shed,
                    "slo_shed_rate": round(slo_rates["shed_rate"], 4),
                    "slo_deadline_miss_rate": round(
                        slo_rates["deadline_miss_rate"], 4
                    ),
                    "slo_p99_ms": round(slo_rates["p99_s"] * 1e3, 3),
                },
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
