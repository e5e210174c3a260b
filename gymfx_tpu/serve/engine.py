"""Batched low-latency policy inference: AOT-compiled bucket ladder.

The training side fuses rollouts into one XLA dispatch per superstep
(train/common.py); this module is the serving twin.  Instead of one
jit-traced batch-of-1 dispatch per decision (the pre-engine live path:
first tick pays the full trace, every tick pays a dispatch),
``InferenceEngine``:

  * AOT-lowers and pre-compiles the actor forward pass for a LADDER of
    padded batch buckets (default 1/8/64/512/4096) at construction via
    ``jax.jit(...).lower(...).compile()`` — boot pays every compile, the
    serving path never traces;
  * serves any request batch by padding it with neutral observations up
    to the smallest covering bucket and unpadding the responses, so N
    concurrent sessions share ONE device dispatch instead of N;
  * donates the observation/carry input buffers on TPU (they are
    rebuilt per dispatch, so XLA may reuse their HBM for the outputs);
  * supports every policy family in train/policies.py through the
    uniform ``apply_seq`` surface — recurrent policies stream their
    (c, h) carry through the engine per session.

Two in-graph batching modes (``batch_mode``):

  ``exact``   rows are computed by a ``lax.map`` of the SINGLE-example
      program — each response is bit-identical to the unbatched
      ``policy.apply`` on the same observation, at every bucket size,
      on every backend (tests/test_serve_engine.py).  One dispatch per
      micro-batch; row compute is sequential in-graph.
  ``matmul``  rows are vmapped into full-width batched GEMMs — the MXU
      throughput mode.  Responses may differ from the unbatched matvec
      program (and, on CPU, across bucket sizes) by float
      reassociation where the backend picks per-shape GEMM
      accumulation strategies; on TPU every bucket lowers to the same
      MXU tiling, so rows are bit-stable across bucket sizes there.
  ``auto``    ``matmul`` on TPU, ``exact`` elsewhere.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np

DEFAULT_BUCKETS: Tuple[int, ...] = (1, 8, 64, 512, 4096)


class WeightSwapError(RuntimeError):
    """A hot-swap was rejected (shape/dtype/tree mismatch against the
    compiled ladder, or a late compile during the swap probe).  The
    engine keeps serving the previous weights — a rejected swap is
    never destructive."""


class Decision(NamedTuple):
    """One response row.  ``actor_out`` is the raw actor head output —
    logits ``(n_actions,)`` for discrete policies, the Gaussian mean for
    continuous ones — so callers can audit the decision; ``action`` is
    the greedy env-action int (0 hold / 1 long / 2 short), already
    thresholded for continuous policies the way the env coerces them."""

    action: Any
    value: Any
    actor_out: Any
    carry: Any


class EngineDispatch:
    """An issued, not-yet-materialized engine dispatch.

    ``dispatch_async`` returns one of these immediately after handing
    the padded batch to the device: JAX dispatch is asynchronous, so the
    caller (the pipelined micro-batcher) can assemble and dispatch the
    NEXT batch while this one's executable is still running.
    :meth:`resolve` blocks on the outputs (one ``device_get``), unpads
    them, and — in slot mode with the mirror enabled — records the
    fetched carry rows into the slot cache's host mirror on the same
    fetch.  Idempotent: resolving twice returns the same Decision.
    """

    __slots__ = ("_engine", "_n", "_outputs", "_carry", "_sessions",
                 "_mode", "_resolved")

    def __init__(self, engine, n, outputs, carry, sessions, mode):
        self._engine = engine
        self._n = int(n)
        self._outputs = outputs   # (action, value, actor_out) device arrays
        self._carry = carry       # device carry rows (or None)
        self._sessions = sessions  # per-row session ids (slot mode)
        self._mode = mode         # "slots" | "host"
        self._resolved = None

    @property
    def n(self) -> int:
        return self._n

    def resolve(self) -> "Decision":
        if self._resolved is not None:
            return self._resolved
        import jax

        engine = self._engine
        n = self._n
        if self._mode == "slots":
            if self._carry is not None:
                action, value, actor_out, carry2 = jax.device_get(
                    (*self._outputs, self._carry)
                )
                cache = engine.slot_cache
                if cache is not None:
                    cache.update_mirror(self._sessions, carry2)
                engine.mirror_fetch_bytes += sum(
                    np.asarray(leaf).nbytes
                    for leaf in jax.tree.leaves(carry2)
                )
            else:
                action, value, actor_out = jax.device_get(self._outputs)
            # carry stays device-resident: None here is the slot-mode
            # contract (the mirror is the host view of session carry)
            decision = Decision(
                np.asarray(action)[:n],
                np.asarray(value)[:n],
                np.asarray(actor_out)[:n],
                None,
            )
        else:
            action, value, actor_out, carry2 = jax.device_get(
                (*self._outputs, self._carry)
            )
            decision = Decision(
                np.asarray(action)[:n],
                np.asarray(value)[:n],
                np.asarray(actor_out)[:n],
                jax.tree.map(lambda x: np.asarray(x)[:n], carry2)
                if engine.recurrent
                else carry2,
            )
        self._resolved = decision
        return decision


def resolve_batch_mode(mode: str) -> str:
    """'auto' -> 'matmul' on TPU (MXU batching), 'exact' elsewhere
    (bit-identity guaranteed; CPU GEMM kernels reassociate)."""
    if mode not in ("auto", "exact", "matmul"):
        raise ValueError(
            f"serve batch_mode must be auto|exact|matmul, got {mode!r}"
        )
    if mode != "auto":
        return mode
    from gymfx_tpu.ops.dispatch import on_tpu

    return "matmul" if on_tpu() else "exact"


class InferenceEngine:
    """AOT-compiled, shape-bucketed batched policy forward pass.

    Parameters
    ----------
    policy : a train/policies.py module (any family)
    params : its variables (e.g. from train/checkpoint.py load_params)
    example_obs_vec : one encoded observation — the flat ``(obs_dim,)``
        vector (flatten_obs) or ``(window, token_dim)`` token block
        (tokens_from_obs) — fixing the request shape/dtype
    buckets : padded batch ladder; compiled at construction when
        ``warmup=True`` (the default — serving must never trace)
    batch_mode : 'auto' | 'exact' | 'matmul' (see module docstring)
    continuous : the policy emits a (mu, log_std) Gaussian head; greedy
        actions are thresholded at ``continuous_threshold`` exactly like
        the env coerces continuous actions (core/env.py)
    neutral_obs : the pad row (defaults to zeros — the scaled-feature
        neutral); never visible in responses
    donate : donate obs/carry input buffers to the executable
        (default: only on TPU — CPU ignores donation with a warning)
    """

    def __init__(
        self,
        policy: Any,
        params: Any,
        example_obs_vec: Any,
        *,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        batch_mode: str = "auto",
        continuous: bool = False,
        continuous_threshold: float = 0.33,
        neutral_obs: Optional[np.ndarray] = None,
        donate: Optional[bool] = None,
        warmup: bool = True,
    ):
        import jax
        import jax.numpy as jnp

        if not buckets:
            raise ValueError("bucket ladder must not be empty")
        self.policy = policy
        self.buckets = tuple(sorted({int(b) for b in buckets}))
        if self.buckets[0] < 1:
            raise ValueError(f"bucket sizes must be >= 1, got {self.buckets}")
        self.batch_mode = resolve_batch_mode(batch_mode)
        self.continuous = bool(continuous)
        self.continuous_threshold = float(continuous_threshold)
        self.params = jax.device_put(params)

        obs = np.asarray(example_obs_vec)
        self.obs_shape = tuple(int(s) for s in obs.shape)
        self.obs_dtype = np.dtype(obs.dtype)
        if neutral_obs is None:
            neutral_obs = np.zeros(self.obs_shape, self.obs_dtype)
        self.neutral_obs = np.asarray(neutral_obs, self.obs_dtype)
        if self.neutral_obs.shape != self.obs_shape:
            raise ValueError(
                f"neutral_obs shape {self.neutral_obs.shape} != "
                f"observation shape {self.obs_shape}"
            )

        carry0 = policy.initial_carry(())
        self._carry_leaves = jax.tree.leaves(carry0)
        self.recurrent = len(self._carry_leaves) > 0
        self._carry0 = jax.tree.map(lambda x: np.asarray(x), carry0)

        if donate is None:
            from gymfx_tpu.ops.dispatch import on_tpu

            donate = on_tpu()
        donate_argnums = (1, 2) if donate else ()

        thr = jnp.float32(self.continuous_threshold)
        cont = self.continuous

        def single(params, obs_row, carry_row):
            out, value, carry2 = policy.apply_seq(params, obs_row, carry_row)
            if cont:
                mu, _log_std = out
                action = jnp.where(
                    mu >= thr, 1, jnp.where(mu <= -thr, 2, 0)
                ).astype(jnp.int32)
                actor_out = mu
            else:
                action = jnp.argmax(out, axis=-1).astype(jnp.int32)
                actor_out = out
            return action, value, actor_out, carry2

        if self.batch_mode == "exact":

            def batched(params, obs_b, carry_b):
                return jax.lax.map(
                    lambda row: single(params, row[0], row[1]),
                    (obs_b, carry_b),
                )

        elif getattr(policy, "takes_batch", False):
            # a policy that takes the batch itself (an expert layer sorts
            # the tokens of the whole bucket) is called on it as it is
            batched = single
        else:

            def batched(params, obs_b, carry_b):
                return jax.vmap(single, in_axes=(None, 0, 0))(
                    params, obs_b, carry_b
                )

        self._batched = batched
        self.donate = bool(donate)
        self._fwd = jax.jit(batched, donate_argnums=donate_argnums)
        self._compiled: Dict[int, Any] = {}
        # ---- device-resident session slots (serve/slots.py) ----
        # all None/empty until enable_slots(); the host-carry serving
        # path above never consults them, so with serve_session_slots
        # unset the engine behaves bitwise as before
        self.slot_cache = None
        self._fwd_slots = None
        self._compiled_slots: Dict[int, Any] = {}
        self._seed_fn = None
        self._obs_staging: Dict[int, list] = {}
        self._staging_flip = 0
        self.slot_dispatches = 0
        self.slot_decisions = 0
        self.mirror_fetch_bytes = 0   # carry bytes fetched for the mirror
        self.seed_upload_bytes = 0    # carry bytes uploaded to seed slots
        # serialized against concurrent decide_batch callers: the
        # executables are stateless but the late-compile bookkeeping and
        # jax dispatch are cheapest kept single-file (the MicroBatcher
        # owns the one dispatch thread in the serving topology anyway)
        self._lock = threading.Lock()
        self.late_compiles = 0  # compiles after boot — a warm engine has 0
        self.generation = 0     # bumped on every accepted swap_weights
        self.swap_count = 0
        # compile-watch hook: called as on_compile(bucket, duration_s,
        # late) after every bucket compile (CompileWatch.watch_engine
        # attaches it; None costs nothing)
        self.on_compile = None
        if warmup:
            self.warmup()

    # ------------------------------------------------------------------
    def _zero_batch(self, bucket: int):
        obs = np.broadcast_to(
            self.neutral_obs, (bucket, *self.obs_shape)
        ).copy()
        carry = self.initial_carry_batch(bucket)
        return obs, carry

    def initial_carry_batch(self, n: int):
        """Fresh (zero) recurrent carry for ``n`` sessions, host-side."""
        import jax

        return jax.tree.map(
            lambda x: np.broadcast_to(x, (n, *x.shape)).copy(), self._carry0
        )

    def initial_carry(self):
        """Fresh per-session carry (host-side numpy leaves)."""
        import jax

        return jax.tree.map(np.copy, self._carry0)

    def warmup(self) -> None:
        """AOT-compile every ladder bucket and run each once (the first
        execution also pays allocator/autotune setup).  Idempotent."""
        for bucket in self.buckets:
            if bucket in self._compiled:
                continue
            t0 = time.perf_counter()
            exe = self._fwd.lower(
                self.params, *self._zero_batch(bucket)
            ).compile()
            compile_s = time.perf_counter() - t0
            # one throwaway execution per bucket: boot absorbs every
            # first-call cost, the serving path never does
            exe(self.params, *self._zero_batch(bucket))
            self._compiled[bucket] = exe
            if self.on_compile is not None:
                self.on_compile(bucket, compile_s, False)

    @property
    def executable_count(self) -> int:
        return len(self._compiled)

    # ------------------------------------------------------------------
    def swap_weights(self, params: Any, *, probe: bool = True) -> int:
        """Hot-swap the served weights without recompiling the ladder.

        Honor-or-reject: the candidate must match the compiled
        executables' calling convention exactly — same pytree structure,
        same per-leaf shape and dtype — or :class:`WeightSwapError` is
        raised and the engine keeps serving the previous weights.  The
        flip itself happens under the dispatch lock, so every in-flight
        ``decide_batch`` completes against exactly one weight set (the
        executables never donate the params argument — donation covers
        obs/carry only — so the old weights stay valid until the last
        dispatch holding them returns).

        With ``probe=True`` (default) the smallest compiled bucket is
        dispatched once against the new weights while the lock is held;
        any exception or late compile during the probe restores the old
        params and raises — a swap can never leave the ladder cold.

        Returns the new generation number (monotonic, starts at 0).
        """
        import jax

        new_leaves, new_tree = jax.tree.flatten(params)
        cur_leaves, cur_tree = jax.tree.flatten(self.params)
        if new_tree != cur_tree:
            raise WeightSwapError(
                f"params tree structure mismatch: engine serves "
                f"{cur_tree}, candidate is {new_tree}"
            )
        for i, (new, cur) in enumerate(zip(new_leaves, cur_leaves)):
            ns, nd = _leaf_signature(new)
            cs, cd = _leaf_signature(cur)
            if ns != cs or nd != cd:
                raise WeightSwapError(
                    f"params leaf {i} mismatch: engine serves "
                    f"shape={cs} dtype={cd}, candidate has "
                    f"shape={ns} dtype={nd} — same-shape swaps only "
                    f"(the AOT ladder is compiled for one signature)"
                )
        new_params = jax.device_put(params)  # transfer outside the lock
        with self._lock:
            old_params = self.params
            before = self.late_compiles
            self.params = new_params
            if probe and self._compiled:
                bucket = min(self._compiled)
                try:
                    out = self._dispatch(*self._zero_batch(bucket), bucket)
                    jax.block_until_ready(out)
                except Exception as exc:
                    self.params = old_params
                    raise WeightSwapError(
                        f"swap probe dispatch failed on bucket {bucket}: "
                        f"{exc}"
                    ) from exc
                if self.late_compiles != before:
                    self.params = old_params
                    raise WeightSwapError(
                        "late compile during weight swap — the candidate "
                        "does not fit the compiled ladder (hard failure "
                        "by contract; previous weights restored)"
                    )
            self.generation += 1
            self.swap_count += 1
            return self.generation

    def bucket_for(self, n: int) -> int:
        """Smallest ladder bucket covering ``n`` requests (the largest
        bucket when ``n`` exceeds the ladder — decide_batch then splits
        the batch into max-bucket chunks)."""
        if n < 1:
            raise ValueError(f"batch size must be >= 1, got {n}")
        for bucket in self.buckets:
            if bucket >= n:
                return bucket
        return self.buckets[-1]

    # ------------------------------------------------------------------
    def _dispatch(self, obs_pad: np.ndarray, carry_pad: Any, bucket: int):
        exe = self._compiled.get(bucket)
        if exe is None:
            # never hit after warmup() with a covering ladder; counted so
            # the zero-compiles-after-boot contract is testable
            t0 = time.perf_counter()
            exe = self._fwd.lower(self.params, obs_pad, carry_pad).compile()
            self._compiled[bucket] = exe
            self.late_compiles += 1
            if self.on_compile is not None:
                self.on_compile(bucket, time.perf_counter() - t0, True)
        return exe(self.params, obs_pad, carry_pad)

    def decide_batch(self, obs_batch: Any, carries: Any = None):
        """Decide for ``n`` concurrent requests in one device dispatch.

        ``obs_batch``: (n, *obs_shape) stacked encoded observations (or
        a sequence of rows).  ``carries``: stacked recurrent carry with
        leading dim n (required for recurrent policies; must be None or
        () otherwise).  Returns a :class:`Decision` of stacked numpy
        arrays with leading dim exactly n — pad rows are computed and
        discarded here, they can never leak to a caller.
        """
        import jax

        obs = np.asarray(obs_batch, self.obs_dtype)
        if obs.ndim == len(self.obs_shape):  # single row convenience
            obs = obs[None]
        if obs.shape[1:] != self.obs_shape:
            raise ValueError(
                f"obs batch shape {obs.shape} does not match "
                f"(n, {', '.join(map(str, self.obs_shape))})"
            )
        n = int(obs.shape[0])
        if self.recurrent:
            if carries is None:
                raise ValueError(
                    "recurrent policy: decide_batch needs the stacked "
                    "session carries (engine.initial_carry_batch(n) for "
                    "fresh sessions)"
                )
            carry = jax.tree.map(lambda x: np.asarray(x), carries)
        else:
            carry = self._carry0

        bucket = self.bucket_for(n)
        if n > bucket:  # ladder exceeded: chunk by the largest bucket
            outs = [
                self.decide_batch(
                    obs[i : i + bucket],
                    jax.tree.map(lambda x: x[i : i + bucket], carry)
                    if self.recurrent
                    else None,
                )
                for i in range(0, n, bucket)
            ]
            return Decision(
                *(
                    jax.tree.map(lambda *xs: np.concatenate(xs), *field)
                    if i == 3
                    else np.concatenate(field)
                    for i, field in enumerate(zip(*outs))
                )
            )

        obs_pad = np.empty((bucket, *self.obs_shape), self.obs_dtype)
        obs_pad[:n] = obs
        obs_pad[n:] = self.neutral_obs
        if self.recurrent:
            pad_carry = self.initial_carry_batch(bucket)
            carry_pad = jax.tree.map(
                lambda full, got: _fill_rows(full, got, n), pad_carry, carry
            )
        else:
            carry_pad = self._carry0

        with self._lock:
            action, value, actor_out, carry2 = self._dispatch(
                obs_pad, carry_pad, bucket
            )
        action, value, actor_out, carry2 = jax.device_get(
            (action, value, actor_out, carry2)
        )
        return Decision(
            np.asarray(action)[:n],
            np.asarray(value)[:n],
            np.asarray(actor_out)[:n],
            jax.tree.map(lambda x: np.asarray(x)[:n], carry2)
            if self.recurrent
            else carry2,
        )

    def decide(self, obs_vec: Any, carry: Any = None) -> Decision:
        """Single-request convenience: one row through the bucket-1
        executable (or the smallest bucket in the ladder)."""
        import jax

        carries = None
        if self.recurrent:
            if carry is None:
                carry = self.initial_carry()
            carries = jax.tree.map(lambda x: np.asarray(x)[None], carry)
        out = self.decide_batch(np.asarray(obs_vec)[None], carries)
        return Decision(
            out.action[0],
            out.value[0],
            out.actor_out[0],
            jax.tree.map(lambda x: x[0], out.carry)
            if self.recurrent
            else out.carry,
        )

    # ------------------------------------------------------------------
    # device-resident session slots (serve/slots.py, docs/serving.md
    # "Device-resident sessions") — a parallel AOT ladder whose fused
    # gather→policy→scatter program keeps recurrent carry on device.
    # The host-carry path above is untouched: with serve_session_slots
    # unset none of this is compiled or consulted.
    def enable_slots(self, n_slots: int, *, mirror: bool = True):
        """Pre-allocate the device slot arrays and AOT-compile the fused
        slot ladder (one executable per bucket, like :meth:`warmup`).
        Idempotent for the same capacity; a no-op (returns None) on
        stateless policies, which have no carry to cache.  Returns the
        :class:`~gymfx_tpu.serve.slots.SlotCache`."""
        import jax

        if not self.recurrent:
            return None
        if self.slot_cache is not None:
            if self.slot_cache.slots != int(n_slots):
                raise ValueError(
                    f"slot cache already enabled with "
                    f"{self.slot_cache.slots} slots (asked for {n_slots})"
                )
            return self.slot_cache
        from gymfx_tpu.serve.slots import SlotCache

        cache = SlotCache(int(n_slots), self._carry0, mirror=mirror)
        batched = self._batched

        def fused(params, state, obs_b, gather_idx, scatter_idx):
            carry_b = jax.tree.map(lambda s: s[gather_idx], state)
            action, value, actor_out, carry2 = batched(
                params, obs_b, carry_b
            )
            new_state = jax.tree.map(
                lambda s, c: s.at[scatter_idx].set(c), state, carry2
            )
            return action, value, actor_out, carry2, new_state

        def seed(state, slot, carry_row):
            return jax.tree.map(
                lambda s, c: s.at[slot].set(c.astype(s.dtype)),
                state,
                carry_row,
            )

        # donate the slot state (rebuilt by every dispatch: scatter is
        # then in place) and the padded obs; TPU only, like the host
        # ladder — CPU ignores donation with a warning
        self._fwd_slots = jax.jit(
            fused, donate_argnums=(1, 2) if self.donate else ()
        )
        self._seed_fn = jax.jit(
            seed, donate_argnums=(0,) if self.donate else ()
        )
        self.slot_cache = cache
        self.warmup_slots()
        # one throwaway seed into SCRATCH compiles the seeder at boot
        cache.state = self._seed_fn(
            cache.state, np.int32(cache.scratch_row), self.initial_carry()
        )
        return cache

    def warmup_slots(self) -> None:
        """AOT-compile the fused slot ladder for every bucket and run
        each once (gathering INITIAL, scattering SCRATCH — session rows
        are untouched).  Idempotent."""
        if self.slot_cache is None:
            return
        cache = self.slot_cache
        for bucket in self.buckets:
            if bucket in self._compiled_slots:
                continue
            obs = np.broadcast_to(
                self.neutral_obs, (bucket, *self.obs_shape)
            ).copy()
            gather = np.full(bucket, cache.initial_row, np.int32)
            scatter = np.full(bucket, cache.scratch_row, np.int32)
            t0 = time.perf_counter()
            exe = self._fwd_slots.lower(
                self.params, cache.state, obs, gather, scatter
            ).compile()
            compile_s = time.perf_counter() - t0
            out = exe(self.params, cache.state, obs, gather, scatter)
            cache.state = out[4]
            self._compiled_slots[bucket] = exe
            if self.on_compile is not None:
                self.on_compile(bucket, compile_s, False)

    def _dispatch_slots(
        self,
        obs_pad: np.ndarray,
        gather_idx: np.ndarray,
        scatter_idx: np.ndarray,
        bucket: int,
    ):
        exe = self._compiled_slots.get(bucket)
        cache = self.slot_cache
        if exe is None:
            t0 = time.perf_counter()
            exe = self._fwd_slots.lower(
                self.params, cache.state, obs_pad, gather_idx, scatter_idx
            ).compile()
            self._compiled_slots[bucket] = exe
            self.late_compiles += 1
            if self.on_compile is not None:
                self.on_compile(bucket, time.perf_counter() - t0, True)
        return exe(self.params, cache.state, obs_pad, gather_idx, scatter_idx)

    def _staged_pad(self, obs: np.ndarray, n: int, bucket: int) -> np.ndarray:
        """Pad ``obs`` into a double-buffered host staging buffer
        (alternating per dispatch).  Safe with pipeline depth one: a
        buffer is rewritten two dispatches later, after the dispatch
        that referenced it has been resolved — so even a backend that
        aliases host numpy inputs never sees a concurrent rewrite.
        Callers must hold the dispatch lock."""
        bufs = self._obs_staging.get(bucket)
        if bufs is None:
            bufs = [
                np.empty((bucket, *self.obs_shape), self.obs_dtype)
                for _ in range(2)
            ]
            for b in bufs:
                b[:] = self.neutral_obs
            self._obs_staging[bucket] = bufs
        self._staging_flip ^= 1
        buf = bufs[self._staging_flip]
        buf[:n] = obs
        buf[n:] = self.neutral_obs
        return buf

    def dispatch_async(
        self,
        obs_batch: Any,
        carries: Any = None,
        *,
        sessions: Optional[Sequence[Optional[str]]] = None,
        seed_carries: Optional[Sequence[Any]] = None,
    ) -> EngineDispatch:
        """Issue one dispatch WITHOUT materializing the outputs; returns
        an :class:`EngineDispatch` whose ``resolve()`` blocks on them.

        With the slot cache enabled and per-row ``sessions`` given, the
        fused slot ladder runs: carry is gathered from and scattered to
        the device slots (zero per-decision carry transfer; a new
        session's slot is seeded from ``seed_carries[i]`` when provided
        — the failover re-pin — else from the initial carry).  Rows with
        ``sessions[i] is None`` compute from the initial carry and leave
        no state behind.  Otherwise the host-carry semantics of
        :meth:`decide_batch` apply (``carries`` defaults to the initial
        batch for recurrent policies).  The batch must fit the ladder:
        the async path never chunks.
        """
        import jax

        obs = np.asarray(obs_batch, self.obs_dtype)
        if obs.ndim == len(self.obs_shape):
            obs = obs[None]
        if obs.shape[1:] != self.obs_shape:
            raise ValueError(
                f"obs batch shape {obs.shape} does not match "
                f"(n, {', '.join(map(str, self.obs_shape))})"
            )
        n = int(obs.shape[0])
        bucket = self.bucket_for(n)
        if n > bucket:
            raise ValueError(
                f"async dispatch of {n} rows exceeds the largest bucket "
                f"{bucket} (the async path never chunks)"
            )
        cache = self.slot_cache
        if cache is not None and self.recurrent and sessions is not None:
            sessions = [None if s is None else str(s) for s in sessions]
            if len(sessions) != n:
                raise ValueError(
                    f"{len(sessions)} sessions for {n} obs rows"
                )
            with self._lock:
                gather, scatter, seeds = cache.assign(
                    bucket, sessions, seed_carries
                )
                for slot, carry in seeds:
                    row = jax.tree.map(np.asarray, carry)
                    cache.state = self._seed_fn(
                        cache.state, np.int32(slot), row
                    )
                    self.seed_upload_bytes += sum(
                        leaf.nbytes for leaf in jax.tree.leaves(row)
                    )
                obs_pad = self._staged_pad(obs, n, bucket)
                out = self._dispatch_slots(obs_pad, gather, scatter, bucket)
                cache.state = out[4]
                self.slot_dispatches += 1
                self.slot_decisions += n
            carry_out = out[3] if cache.mirror_enabled else None
            return EngineDispatch(
                self, n, out[:3], carry_out, sessions, "slots"
            )
        # host-carry async path (stateless engines, or explicit carries)
        if self.recurrent:
            if carries is None:
                carries = self.initial_carry_batch(n)
            carry = jax.tree.map(lambda x: np.asarray(x), carries)
            pad_carry = self.initial_carry_batch(bucket)
            carry_pad = jax.tree.map(
                lambda full, got: _fill_rows(full, got, n), pad_carry, carry
            )
        else:
            carry_pad = self._carry0
        with self._lock:
            obs_pad = self._staged_pad(obs, n, bucket)
            out = self._dispatch(obs_pad, carry_pad, bucket)
        return EngineDispatch(self, n, out[:3], out[3], None, "host")

    def decide_batch_slots(
        self,
        obs_batch: Any,
        sessions: Sequence[Optional[str]],
        seed_carries: Optional[Sequence[Any]] = None,
    ) -> Decision:
        """Synchronous slot-mode decide: one fused dispatch, resolved
        immediately.  Decision.carry is None — carry stays on device
        (the mirror holds the host view)."""
        return self.dispatch_async(
            obs_batch, sessions=sessions, seed_carries=seed_carries
        ).resolve()

    def slot_stats(self) -> Dict[str, Any]:
        """Slot-cache counters for telemetry and the bench contract."""
        out = {
            "enabled": self.slot_cache is not None,
            "slot_dispatches": self.slot_dispatches,
            "slot_decisions": self.slot_decisions,
            "mirror_fetch_bytes": self.mirror_fetch_bytes,
            "seed_upload_bytes": self.seed_upload_bytes,
        }
        if self.slot_cache is not None:
            out.update(self.slot_cache.stats())
        return out


def _leaf_signature(leaf: Any) -> Tuple[Tuple[int, ...], str]:
    """(shape, dtype-name) of a params leaf without forcing a host copy
    — works for jax arrays (incl. bfloat16), numpy, and python scalars."""
    shape = tuple(int(s) for s in getattr(leaf, "shape", np.shape(leaf)))
    dtype = getattr(leaf, "dtype", None)
    if dtype is None:
        dtype = np.asarray(leaf).dtype
    return shape, str(dtype)


def _fill_rows(full: np.ndarray, got: np.ndarray, n: int) -> np.ndarray:
    full = np.asarray(full)
    full[:n] = np.asarray(got, full.dtype)
    return full


# ---------------------------------------------------------------------------
# construction from the training stack
# ---------------------------------------------------------------------------
class EngineBundle(NamedTuple):
    """A warm engine plus everything needed to feed it requests."""

    engine: "InferenceEngine"
    env: Any              # the bound core.runtime.Environment
    policy_name: str
    obs_spec: Any         # train/policies.py ObsSpec
    encode: Any           # obs dict -> engine input row (jnp encoder)
    reset_obs: Any        # the env's reset observation (shape template)


def engine_from_config(
    config: Dict[str, Any],
    *,
    params: Optional[Any] = None,
    env: Optional[Any] = None,
    warmup: bool = True,
) -> "EngineBundle":
    """Build a warm engine (plus its featurizer inputs) from the merged
    config dict — the one construction path shared by the live router
    boot (live/oanda.py PolicyDecisionService) and bench_infer.py.

    Resolves the policy exactly like the trainers (same
    make_trainer_policy path, same encoded obs layout), loads params
    from ``checkpoint_dir`` when present (honoring the checkpoint's
    recorded architecture), else initializes fresh ones — a serving
    stack must be bootable without a trained model for load tests.
    """
    import jax

    from gymfx_tpu.core import env as env_core
    from gymfx_tpu.core.runtime import Environment
    from gymfx_tpu.serve.config import serve_config_from
    from gymfx_tpu.train.policies import (
        make_obs_encoder,
        make_obs_spec,
        make_trainer_policy,
        policy_kwargs_from,
    )

    scfg = serve_config_from(config)
    if env is None:
        env = Environment(config)
    policy_name = str(config.get("policy") or "mlp")
    policy_kwargs = policy_kwargs_from(config)
    ckpt_dir = config.get("checkpoint_dir")
    if ckpt_dir:
        from gymfx_tpu.train.checkpoint import read_metadata

        meta = read_metadata(str(ckpt_dir))
        if not config.get("policy") and meta.get("policy"):
            policy_name = str(meta["policy"])
            policy_kwargs = dict(meta.get("policy_kwargs") or policy_kwargs)

    dtype_name = str(config.get("policy_dtype", "float32"))
    import jax.numpy as jnp

    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype_name]
    continuous = (
        str(config.get("action_space_mode", "discrete")) == "continuous"
    )
    policy = make_trainer_policy(
        policy_name,
        continuous=continuous,
        dtype=dtype,
        kwargs=policy_kwargs,
        window=env.cfg.window_size,
    )

    data = (
        env.require_resident_data("serving boot (reset obs template)")
        if hasattr(env, "require_resident_data")
        else env.data
    )
    _state, reset_obs = env_core.reset(env.cfg, env.params, data)
    spec = make_obs_spec(reset_obs)
    encode = make_obs_encoder(policy_name, env.cfg.window_size, spec)
    example_vec = np.asarray(encode(reset_obs))

    if params is None:
        if ckpt_dir:
            from gymfx_tpu.train.checkpoint import load_params

            params, _step = load_params(str(ckpt_dir))
        else:
            key = jax.random.PRNGKey(int(config.get("seed", 0) or 0))
            carry0 = policy.initial_carry(())
            if len(jax.tree.leaves(carry0)) > 0:
                params = policy.init(key, example_vec, carry0)
            else:
                params = policy.init(key, example_vec)

    engine = InferenceEngine(
        policy,
        params,
        example_vec,
        buckets=scfg.buckets,
        batch_mode=scfg.batch_mode,
        continuous=continuous,
        continuous_threshold=float(
            config.get("continuous_action_threshold", 0.33) or 0.33
        ),
        warmup=bool(warmup and scfg.warmup),
    )
    if scfg.session_slots > 0 and warmup and scfg.warmup:
        # device-resident session carry (serve/slots.py) — a no-op for
        # stateless policies; skipped on warmup=False boots (the slot
        # ladder, like the host ladder, must never compile lazily in
        # serving, so a cold boot stays cold)
        engine.enable_slots(scfg.session_slots, mirror=scfg.slot_mirror)
    return EngineBundle(
        engine=engine,
        env=env,
        policy_name=policy_name,
        obs_spec=spec,
        encode=encode,
        reset_obs=reset_obs,
    )
