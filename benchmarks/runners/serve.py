"""The serve runner: the live router's traffic against the serving stack.

Set-up: ``engine_from_config`` (warm boot: every bucket of the ladder
compiles or loads here) -> ``batcher_from_config``; the correctness check
(a seeded sample of sessions through the batcher against the jitted
unbatched ``policy.apply_seq``, each session threading its own carry, a
copy of ``chip_smoke.phase_serve``'s reference); every session of the mix
seeded into its slot; a lead-in of the same traffic that is not judged.
Window: ``loadgen.send`` offers the schedule open loop from THIS thread
(the batcher's threads are the program's); latencies run from due times.

Traced run: the same open loop for the traffic file's ``trace_seconds``
under the profiler, and the batcher's own request records of that window.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

import harness
import loadgen
from tape import ensure_tape


def reference_check(engine, batcher, base, spec, seed):
    """``sessions`` x ``decisions`` through the batcher (one round at a
    time, so that each session's rows arrive in order) against the
    unbatched policy.  Returns (ok, detail); frees the sessions' slots."""
    import jax
    import jax.numpy as jnp

    sessions, rounds = int(spec["sessions"]), int(spec["decisions"])
    rng = np.random.default_rng(seed)
    rows = base[None, None] + float(spec["noise"]) * rng.standard_normal(
        (rounds, sessions, *engine.obs_shape)).astype(engine.obs_dtype)
    names = [f"check-{s}" for s in range(sessions)]
    served = []
    for r in range(rounds):
        futures = [batcher.submit(rows[r, s], session=names[s])
                   for s in range(sessions)]
        served.append([f.result(timeout=120) for f in futures])

    naive = jax.jit(engine.policy.apply_seq)
    ref_value = np.zeros((rounds, sessions), np.float32)
    ref_actor = [[None] * sessions for _ in range(rounds)]
    with jax.default_matmul_precision("highest"):
        for s in range(sessions):
            carry = engine.policy.initial_carry(())
            for r in range(rounds):
                actor, value, carry = naive(engine.params, jnp.asarray(rows[r, s]), carry)
                ref_actor[r][s] = np.asarray(actor, np.float32)
                ref_value[r, s] = float(np.asarray(value, np.float32))
    ref_actor = np.asarray(ref_actor)
    got_value = np.asarray([[np.asarray(d.value, np.float32) for d in row]
                            for row in served]).reshape(rounds, sessions)
    got_actor = np.asarray([[np.asarray(d.actor_out, np.float32) for d in row]
                            for row in served])
    got_action = np.asarray([[int(d.action) for d in row] for row in served])
    # with random weights the largest logit changes on rounding: actions are
    # compared only where the reference's two best logits are further apart
    # than the tolerance on actor_out
    ordered = np.sort(ref_actor, axis=-1)
    clear = (ordered[..., -1] - ordered[..., -2]) > 2 * float(spec["actor_atol"])
    agree = got_action == np.argmax(ref_actor, axis=-1)
    detail = {
        "kind": "unbatched_policy", "sessions": sessions, "decisions": rounds,
        "value_max_abs_diff": float(np.max(np.abs(got_value - ref_value))),
        "actor_out_max_abs_diff": float(np.max(np.abs(got_actor - ref_actor))),
        "actions_clear": int(clear.sum()),
        "actions_clear_agree": int((agree & clear).sum()),
        "actions_agree": int(agree.sum()), "finite": bool(np.isfinite(got_value).all()),
    }
    for name in names:
        engine.slot_cache.drop(name)
    ok = (detail["finite"]
          and detail["value_max_abs_diff"] <= float(spec["value_atol"])
          and detail["actor_out_max_abs_diff"] <= float(spec["actor_atol"])
          and detail["actions_clear_agree"] == detail["actions_clear"])
    return ok, detail


class Stack:
    """The booted serving stack and the mix's sessions and rows."""

    def __init__(self, ctx: harness.Context):
        from gymfx_tpu.serve import batcher_from_config, engine_from_config

        traffic = ctx.cell["traffic"]
        t = time.perf_counter()
        tape = ensure_tape(traffic["tape"])
        config = harness.program_config(
            ctx.cell, input_data_file=str(tape), seed=harness.seed31(ctx.seed))
        bundle = engine_from_config(config)
        self.engine = engine = bundle.engine
        self.boot_s = time.perf_counter() - t
        self.batcher = batcher_from_config(engine, config)
        # batcher_from_config passes no ``keep_records``; the default of
        # 100,000 is less than one window's requests, so the cap is raised
        self.batcher._records_cap = 10_000_000
        self.sessions, self.pool_rows = int(traffic["sessions"]), int(traffic["row_pool"])
        self.base = np.asarray(bundle.encode(bundle.reset_obs), engine.obs_dtype)
        rng = np.random.default_rng(harness.seed31(ctx.seed, 2))
        self.pool = self.base[None] + float(traffic["row_noise"]) * rng.standard_normal(
            (self.pool_rows, *engine.obs_shape)).astype(engine.obs_dtype)
        self.names = [f"s{i}" for i in range(self.sessions)]

    def seed_slots(self) -> float:
        """Every session into its slot, before any window; seconds taken."""
        t = time.perf_counter()
        for f in [self.batcher.submit(self.pool[i % self.pool_rows], session=name)
                  for i, name in enumerate(self.names)]:
            f.result(timeout=120)
        return time.perf_counter() - t

    def offer(self, seed: int, rate: float, lead_in: float, span_s: float,
              answer_s: float, span=None):
        """A lead-in and a window of the open loop at ``rate``; waits up to
        ``answer_s`` for what is still out.  Returns (sent, window start,
        window end, end of the wait)."""
        schedule = loadgen.make_schedule(
            seed, rate, (lead_in, span_s), self.sessions, self.pool_rows)
        session_of, row_of = schedule.session.tolist(), schedule.row.tolist()
        batcher, pool, names = self.batcher, self.pool, self.names

        def submit(i):
            return batcher.submit(pool[row_of[i]], session=names[session_of[i]])

        start = time.perf_counter()
        sent = loadgen.send(schedule, submit, start=start, span=span)
        loadgen.wait_answers(sent, answer_s)
        return sent, start + lead_in, start + lead_in + span_s, time.perf_counter()


def run(ctx: harness.Context) -> dict:
    import jax

    conf, traffic = ctx.cell["config"], ctx.cell["traffic"]
    compiles = harness.CompileCounter()
    stack = Stack(ctx)
    engine, batcher = stack.engine, stack.batcher
    try:
        t = time.perf_counter()
        check_ok, check_detail = reference_check(
            engine, batcher, stack.base, conf["check"], harness.seed31(ctx.seed, 3))
        check_s = time.perf_counter() - t
        seed_s = stack.seed_slots()
        lead_in = float(traffic.get("lead_in_s", 1.0))
        span_s = float(traffic["trace_seconds"]) if ctx.trace else ctx.seconds
        harness.note(setup={"boot_s": stack.boot_s, "check_s": check_s,
                            "seed_slots_s": seed_s, "cache_hits": compiles.hits,
                            "cache_misses": compiles.misses},
                     check=check_detail, buckets=list(engine.buckets),
                     rate=traffic["rate"])
        compiled_before = compiles.count
        trace, end_to_end = {}, {}
        window = (harness.traced_window(ctx.cell["name"], trace) if ctx.trace
                  else contextlib.nullcontext())
        with window:
            end_to_end["setup_s"] = time.perf_counter() + lead_in - ctx.t0
            sent, t0, t1, t_end = stack.offer(
                harness.seed31(ctx.seed, 4), float(traffic["rate"]), lead_in, span_s,
                float(traffic.get("answer_s", 10.0)),
                span=jax.profiler.TraceAnnotation if ctx.trace else None)
        records = [r for r in batcher.records if t0 <= r.t_enqueue < t1]
    finally:
        batcher.close(timeout=30)

    summary = loadgen.summarise(sent, t0, t1, t_end)
    end_to_end["decision_p95_ms"] = summary["latency_ms"][95]
    end_to_end["decisions_per_s"] = summary["decisions_per_s"]
    stats = engine.slot_stats()
    counters = {
        "late_compiles": int(engine.late_compiles), "batch_mode": engine.batch_mode,
        "donate": bool(engine.donate), "dispatches": int(batcher.dispatches),
        "compiled_inside_window": compiles.count - compiled_before,
        "shed": int(batcher.shed_count), "refused": sent.refused,
        "errors": len(sent.error), "slots": stats,
    }
    correct = (check_ok and counters["late_compiles"] == 0
               and counters["batch_mode"] == conf["expect"]["batch_mode"]
               and counters["donate"] == conf["expect"]["donate"]
               and counters["compiled_inside_window"] == 0
               and stats["evictions"] == 0 and summary["attempted"] > 0)
    harness.note(window=summary, counters=counters, check_ok=check_ok)
    return {"correct": correct, "attempted": summary["attempted"],
            "failed": summary["failed"], "end_to_end": end_to_end, "trace": trace,
            "spans": {"generator_late_ms": summary["generator_late_ms"]},
            "counters": counters, "records": records, "cell": ctx.cell}
