#!/usr/bin/env python3
"""Open-loop sustained-load harness for the serving stack — prints ONE
JSON line (metric ``serve_load_decisions_per_sec``) and writes a
schema-pinned ``serve_load_report.json``.

Unlike bench_infer.py's closed client loops (each client waits for its
response before sending the next request), this harness is OPEN-LOOP:
arrivals follow a fixed target-rate schedule regardless of how fast the
server answers, so queueing collapse is visible instead of being
absorbed by client back-pressure.  N client threads share the schedule
(each fires at ``rate/clients`` with a phase offset) over a pool of
long-lived sessions; ``--session_mix hot`` skews 80%% of traffic onto
20%% of sessions to exercise the slot cache's LRU tail.

Two phases:

  * parity — a short, fully serial scripted stream run through BOTH
    serve paths: the device-resident slot ladder (``--session_slots``)
    and the host-carry path on the same engine.  In the bit-exact batch
    mode the outputs must match bitwise; the report carries the verdict
    (``slot_parity``).  With slots off the phase degrades to a
    determinism check (same stream twice).
  * load — the open-loop run.  The line reports sustained
    decisions/sec, p50/p99 request latency, shed/deadline-miss rates
    and ``dropped`` (requests that left the harness unaccounted — a
    healthy run reports 0).

Usage: python tools/serve_load.py [--rate R] [--duration_s S]
         [--clients C] [--sessions N] [--session_slots K]
         [--session_mix uniform|hot] [--report PATH] [--quick]
"""
import argparse
import json
import os
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from gymfx_tpu.compile_cache import enable_compile_cache

enable_compile_cache()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="lstm")
    ap.add_argument("--rate", type=float, default=2000.0,
                    help="target arrival rate, decisions/sec (open loop)")
    ap.add_argument("--duration_s", type=float, default=5.0)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--sessions", type=int, default=32,
                    help="long-lived session pool size")
    ap.add_argument("--session_mix", default="uniform",
                    choices=("uniform", "hot"),
                    help="'hot' sends 80%% of traffic to 20%% of sessions")
    ap.add_argument("--session_slots", type=int, default=0,
                    help="device slot-cache capacity (0 = host-carry path)")
    ap.add_argument("--batch_mode", default="exact",
                    choices=("auto", "exact", "matmul"))
    ap.add_argument("--wait_ms", type=float, default=1.0)
    ap.add_argument("--deadline_ms", type=float, default=0.0,
                    help="per-request deadline (0 = none)")
    ap.add_argument("--max_queue", type=int, default=0,
                    help="admission-control queue bound (0 = unbounded)")
    ap.add_argument("--parity_steps", type=int, default=6)
    ap.add_argument("--report", default="serve_load_report.json")
    ap.add_argument("--quick", action="store_true", help="small shapes (CI)")
    args = ap.parse_args()
    if args.quick:
        args.rate = min(args.rate, 400.0)
        args.duration_s = min(args.duration_s, 2.0)
        args.clients = min(args.clients, 4)
        args.sessions = min(args.sessions, 12)

    from gymfx_tpu.bench_util import probe_device

    probe_device()

    import numpy as np
    import jax

    from gymfx_tpu.config import DEFAULT_VALUES
    from gymfx_tpu.serve import (
        OVERLOAD_ERRORS,
        batcher_from_config,
        engine_from_config,
    )

    config = dict(DEFAULT_VALUES)
    config.update(
        input_data_file=os.path.join(
            _REPO, "examples", "data", "eurusd_sample.csv"
        ),
        policy=args.policy,
        serve_batch_mode=args.batch_mode,
        serve_session_slots=args.session_slots,
        serve_max_batch_wait_ms=args.wait_ms,
        window_size=32,
    )
    if args.quick:
        config["serve_buckets"] = [1, 4, 8]
    if args.deadline_ms > 0:
        config["serve_deadline_ms"] = args.deadline_ms
    if args.max_queue > 0:
        config["serve_max_queue"] = args.max_queue

    t0 = time.perf_counter()
    bundle = engine_from_config(config)
    engine = bundle.engine
    boot_s = time.perf_counter() - t0

    base = np.asarray(bundle.encode(bundle.reset_obs), engine.obs_dtype)
    rng = np.random.default_rng(0)
    pool = base[None] + 0.01 * rng.standard_normal(
        (256, *engine.obs_shape)
    ).astype(engine.obs_dtype)

    # --- parity phase: slot ladder vs host carry, fully serial ----------
    # a scripted per-session stream; bitwise comparison is meaningful in
    # the bit-exact batch mode (the default here), advisory otherwise
    par_sessions = min(4, args.sessions)
    par_rows = [
        pool[(t * par_sessions) % 200:][:par_sessions]
        for t in range(args.parity_steps)
    ]
    slot_parity = True
    if engine.recurrent and engine.slot_cache is not None:
        host_carry = engine.initial_carry_batch(par_sessions)
        names = [f"parity-{i}" for i in range(par_sessions)]
        for t in range(args.parity_steps):
            d_host = engine.decide_batch(par_rows[t], host_carry)
            host_carry = d_host.carry
            d_slot = engine.decide_batch_slots(par_rows[t], names)
            ok = (
                np.array_equal(d_host.action, d_slot.action)
                and np.array_equal(d_host.value, d_slot.value)
                and np.array_equal(d_host.actor_out, d_slot.actor_out)
            )
            slot_parity = slot_parity and ok
        for s in names:  # leave every slot free for the load phase
            engine.slot_cache.drop(s)
    else:
        carries = (
            engine.initial_carry_batch(par_sessions)
            if engine.recurrent else None
        )
        c1, c2 = carries, carries
        for t in range(args.parity_steps):
            d1 = engine.decide_batch(par_rows[t], c1)
            d2 = engine.decide_batch(par_rows[t], c2)
            c1, c2 = d1.carry, d2.carry
            slot_parity = slot_parity and np.array_equal(
                d1.action, d2.action
            )

    # --- load phase: open-loop arrivals over a session pool -------------
    batcher = batcher_from_config(engine, config)
    use_slots = engine.slot_cache is not None and engine.recurrent

    session_names = [f"load-{i}" for i in range(args.sessions)]
    # host-carry mode threads each session's latest resolved carry;
    # open-loop arrivals may reuse a carry while its successor is still
    # in flight — that is the honest cost of not back-pressuring
    carry_of = {
        s: (engine.initial_carry() if engine.recurrent else None)
        for s in session_names
    }
    carry_lock = threading.Lock()
    hot_cut = max(1, args.sessions // 5)

    counts = {"served": 0, "shed": 0, "deadline_miss": 0, "failed": 0}
    counts_lock = threading.Lock()
    offered = [0] * args.clients
    interarrival = args.clients / args.rate

    def pick_session(r: np.random.Generator) -> str:
        if args.session_mix == "hot" and r.random() < 0.8:
            return session_names[int(r.integers(hot_cut))]
        return session_names[int(r.integers(args.sessions))]

    def client(cid: int) -> None:
        r = np.random.default_rng(1000 + cid)
        inflight = []

        def account(fut, sess):
            from gymfx_tpu.serve import DeadlineExceeded, ShedError
            try:
                d = fut.result(timeout=30.0)
                if engine.recurrent and d.carry is not None:
                    with carry_lock:
                        carry_of[sess] = d.carry
                kind = "served"
            except ShedError:
                kind = "shed"
            except DeadlineExceeded:
                kind = "deadline_miss"
            except Exception:
                kind = "failed"
            with counts_lock:
                counts[kind] += 1

        next_t = t_start + cid * interarrival / args.clients
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if now < next_t:
                time.sleep(min(next_t - now, 0.01))
                continue
            sess = pick_session(r)
            row = pool[int(r.integers(len(pool)))]
            try:
                if use_slots:
                    fut = batcher.submit(row, session=sess)
                else:
                    with carry_lock:
                        carry = carry_of[sess]
                    fut = batcher.submit(row, carry, session=sess)
                inflight.append((fut, sess))
            except OVERLOAD_ERRORS:
                with counts_lock:
                    counts["shed"] += 1
            offered[cid] += 1
            next_t += interarrival
            # drain resolved futures opportunistically so the in-flight
            # list stays bounded on long runs
            while inflight and inflight[0][0].done():
                f, s = inflight.pop(0)
                account(f, s)
        for f, s in inflight:
            account(f, s)

    t_start = time.perf_counter() + 0.05
    t_end = t_start + args.duration_s
    threads = [
        threading.Thread(target=client, args=(c,))
        for c in range(args.clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall_s = time.perf_counter() - t_start

    records = batcher.records
    health = batcher.health()
    slot_stats = engine.slot_stats() if hasattr(engine, "slot_stats") else {}
    batcher.close()

    n_offered = sum(offered)
    accounted = sum(counts.values())
    dropped = n_offered - accounted
    lat_ms = np.asarray([r.latency_s for r in records] or [0.0]) * 1e3
    sustained = counts["served"] / wall_s if wall_s > 0 else 0.0

    chips = max(1, jax.local_device_count())
    dev = jax.local_devices()[0]
    platform = str(getattr(dev, "platform", "unknown"))
    device_kind = str(getattr(dev, "device_kind", platform))
    record = {
        "metric": "serve_load_decisions_per_sec",
        "value": round(sustained, 1),
        "unit": f"decisions/sec sustained ({args.policy} policy, "
                f"open-loop {args.rate:.0f}/s target, "
                f"{'slot' if use_slots else 'host-carry'} path)",
        "sustained_decisions_per_sec": round(sustained, 1),
        "target_rate": float(args.rate),
        "offered": n_offered,
        "served": counts["served"],
        "dropped": dropped,
        "shed_rate": round(counts["shed"] / max(n_offered, 1), 4),
        "deadline_miss_rate": round(
            counts["deadline_miss"] / max(n_offered, 1), 4
        ),
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        "clients": args.clients,
        "sessions": args.sessions,
        "session_slots": args.session_slots,
        "slot_parity": bool(slot_parity),
        "duration_s": round(wall_s, 3),
        "comparable": platform not in ("cpu", "unknown"),
        "platform": platform,
        "device_kind": device_kind,
    }
    report = dict(record)
    report.update(
        session_mix=args.session_mix,
        batch_mode=engine.batch_mode,
        boot_compile_s=round(boot_s, 2),
        late_compiles=engine.late_compiles,
        failed=counts["failed"],
        pipeline=bool(health.get("pipeline", False)),
        deferred_count=int(health.get("deferred_count", 0)),
        dispatches=int(health.get("dispatches", 0)),
        mean_coalesced_per_dispatch=round(
            health["coalesced_total"] / health["dispatches"], 2
        ) if health.get("dispatches") else 0.0,
        slot_stats=slot_stats,
    )
    with open(args.report, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(record))


if __name__ == "__main__":
    sys.exit(main())
