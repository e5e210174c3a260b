"""Host-side per-iteration resilience hooks of the training loop.

The jitted train steps carry the in-graph guards (guards.py); this
module is what the one host loop (train/loop.py ``train_loop``, which
PPO, IMPALA and the portfolio trainer all run) calls round them:

  * the SkipMonitor divergence watchdog, run ONE STEP DELAYED — the
    guard counters for iteration ``i`` are fetched only after iteration
    ``i + 1`` has been dispatched, so the async device pipeline never
    stalls on the watchdog's host sync;
  * periodic preemption-safe auto-checkpointing (every
    ``checkpoint_every`` iterations), with the cumulative step count so
    a resumed run keeps advancing past the loaded step;
  * the simulated-preemption kill for checkpoint/resume drills
    (``fault_profile`` ``preempt_at`` clause).

One definition of the hooks, constructed in one place: the loop round
them exists once too, so no trainer's copy can drift.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

from gymfx_tpu.resilience.faults import (
    DeviceLossError,
    SimulatedPreemptionError,
)
from gymfx_tpu.resilience.guards import (
    NonFiniteDivergenceError,
    SkipMonitor,
)

GUARD_METRIC_KEYS = ("nonfinite_skips", "guard_updates", "poisoned_env_resets")

# state_dict_fn: () -> (full state dict to checkpoint, params tree)
StateFn = Callable[[], Tuple[Dict[str, Any], Any]]


class ResilientLoop:
    """Call :meth:`after_step` once per train iteration and
    :meth:`finish` after the loop; raises
    :class:`~gymfx_tpu.resilience.guards.NonFiniteDivergenceError` on
    sustained divergence (after saving a diagnostic checkpoint when a
    checkpoint dir is configured) and
    :class:`~gymfx_tpu.resilience.faults.SimulatedPreemptionError` at
    the injected kill point (after the iteration's checkpoint, so the
    drill resumes from it)."""

    def __init__(
        self,
        *,
        steps_per_iter: int,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 0,
        step_offset: int = 0,
        checkpoint_metadata: Optional[Dict[str, Any]] = None,
        max_consecutive_skips: int = 10,
        preempt_at: Optional[int] = None,
        loggers: Tuple[Any, ...] = (),
        ledger: Any = None,
        recorder: Any = None,
        profiler: Any = None,
        mesh_faults: Tuple[Dict[str, Any], ...] = (),
        supervisor: Any = None,
        checkpoint_keep: int = 0,
    ):
        self.steps_per_iter = int(steps_per_iter)
        self.checkpoint_dir = str(checkpoint_dir) if checkpoint_dir else None
        self.checkpoint_every = int(checkpoint_every or 0)
        self.step_offset = int(step_offset or 0)
        self.checkpoint_metadata = checkpoint_metadata
        self.preempt_at = None if preempt_at is None else int(preempt_at)
        self.monitor = (
            SkipMonitor(max_consecutive_skips)
            if int(max_consecutive_skips or 0) > 0
            else None
        )
        # delayed metric drains (DelayedLogger / DeviceMetricStream)
        # tied to this loop's lifetime: they hold their newest snapshot
        # one dispatch behind, so every abort path below must flush them
        # or the final superstep's metrics are silently dropped
        self.loggers = tuple(loggers)
        # run-forensics taps (both optional, both never-raises by
        # contract): the ledger records lifecycle events, the flight
        # recorder dumps its postmortem bundle on the abort paths
        self.ledger = ledger
        self.recorder = recorder
        # managed jax.profiler capture (telemetry/profiler.py): the loop
        # owns the cadence — begin_superstep opens the trace window,
        # after_superstep closes it and writes the capture bundle
        self.profiler = profiler
        # simulated device loss (fault grammar ``mesh=`` clause,
        # docs/resilience.md "Elastic training"): each event fires at
        # the first superstep boundary reaching its ``at`` iteration —
        # ledger mesh_degrade, flight-recorder dump, then
        # DeviceLossError for the elastic controller to classify
        self._mesh_faults = sorted(
            (dict(f) for f in mesh_faults), key=lambda f: int(f["at"])
        )
        # MeshSupervisor (parallel/elastic.py): told about scripted
        # losses so the gymfx_mesh_devices{state} gauges and the degrade
        # counter move even on CPU virtual meshes where probes still pass
        self.supervisor = supervisor
        # newest-N checkpoint retention (0 = keep everything); the
        # resume-entry step is always protected
        self.checkpoint_keep = int(checkpoint_keep or 0)
        self.last_checkpoint_step: Optional[int] = None
        # (it_start, k, guard metrics) — scalars for k == 1, stacked
        # (k,) arrays for a fused superstep
        self._pending: Optional[Tuple[int, int, Dict[str, Any]]] = None

    def _flush_loggers(self) -> None:
        for logger in self.loggers:
            try:
                logger.finish()
            except Exception:
                # a telemetry drain failure must not mask the abort
                # (or break a clean finish)
                pass

    # ------------------------------------------------------------------
    def _save(self, state_fn: StateFn, step: int) -> None:
        from gymfx_tpu.train.checkpoint import save_checkpoint

        state_dict, params = state_fn()
        save_checkpoint(
            self.checkpoint_dir, state_dict, step=step,
            metadata=self.checkpoint_metadata, params=params,
            keep=self.checkpoint_keep, protect=(self.step_offset,),
        )
        self.last_checkpoint_step = step
        if self.ledger is not None:
            self.ledger.record("checkpoint_write", step=int(step))

    def _check_pending(self, state_fn: StateFn) -> None:
        if self.monitor is None or self._pending is None:
            return
        import jax
        import numpy as np

        it_start, k, guard_metrics = self._pending
        self._pending = None
        # ONE host fetch per superstep: each guard counter arrives as a
        # stacked (k,) array ((1,) for the per-step path) and the
        # monitor replays the per-iteration deltas from it — fetched as
        # one device_get of the whole tree so mesh-sharded counters do
        # not gather per leaf
        host = {
            key: np.ravel(np.asarray(value))
            for key, value in jax.device_get(guard_metrics).items()
        }
        try:
            for j in range(k):
                self.monitor.update(
                    {key: arr[j] for key, arr in host.items()},
                    step=it_start + j,
                )
        except NonFiniteDivergenceError:
            # params are still the last finite values (the in-graph
            # guard kept them) — persist them for the post-mortem/resume
            if self.checkpoint_dir:
                self._save(
                    state_fn,
                    self.step_offset + (it_start + k) * self.steps_per_iter,
                )
            self._flush_loggers()
            if self.ledger is not None:
                self.ledger.record("divergence", it=int(it_start + k))
            if self.recorder is not None:
                self.recorder.dump("divergence",
                                   extra={"it": int(it_start + k)})
            raise

    # ------------------------------------------------------------------
    def begin_superstep(self, it_start: int, k: int = 1) -> bool:
        """Open a profiler capture window when the configured cadence
        says this dispatch is due; returns whether a capture is now
        active — the caller must block the dispatch result before
        :meth:`after_superstep` so the trace covers the device work.
        A no-op (False) without a profiler, so the fast path is one
        attribute check."""
        if self.profiler is None:
            return False
        return self.profiler.start_capture(it_start, k)

    def after_superstep(self, it_start: int, k: int, metrics: Dict[str, Any],
                        state_fn: StateFn) -> None:
        """Superstep-aware hook: call once after dispatching iterations
        ``[it_start, it_start + k)`` as one fused dispatch.  ``metrics``
        carries the per-iteration guard counters stacked on a leading
        ``(k,)`` axis (plain scalars are fine when ``k == 1``).
        ``after_step(it, m, fn)`` is exactly
        ``after_superstep(it, 1, m, fn)``.

        With ``k > 1`` checkpoints land on the first superstep boundary
        at/after each ``checkpoint_every`` multiple (only boundary
        states exist on the host), and the simulated preemption fires on
        the first boundary reaching ``preempt_at``.
        """
        it_end = it_start + k
        if self.ledger is not None:
            self.ledger.record("superstep_dispatch",
                               it_start=int(it_start), k=int(k))
        if self.profiler is not None and self.profiler.capturing:
            # close the window begin_superstep opened (never raises);
            # runs before the watchdog so an abort still gets its bundle
            self.profiler.finish_capture()
        if self.monitor is not None:
            self._check_pending(state_fn)
            self._pending = (
                it_start,
                k,
                {key: metrics[key] for key in GUARD_METRIC_KEYS if key in metrics},
            )
        if (
            self.checkpoint_dir
            and self.checkpoint_every > 0
            and it_end // self.checkpoint_every > it_start // self.checkpoint_every
        ):
            self._save(state_fn, self.step_offset + it_end * self.steps_per_iter)
        if self._mesh_faults and int(self._mesh_faults[0]["at"]) <= it_end:
            due = [f for f in self._mesh_faults if int(f["at"]) <= it_end]
            self._mesh_faults = [
                f for f in self._mesh_faults if int(f["at"]) > it_end
            ]
            lost = sorted({int(f["device"]) for f in due})
            self._flush_loggers()
            if self.supervisor is not None:
                try:
                    self.supervisor.mark_lost(lost)
                except Exception:
                    pass
            if self.ledger is not None:
                self.ledger.record(
                    "mesh_degrade", lost=lost, at=int(it_end),
                    checkpoint_step=self.last_checkpoint_step,
                )
            if self.recorder is not None:
                self.recorder.dump(
                    "device_loss", extra={"lost": lost, "at": int(it_end)}
                )
            raise DeviceLossError(
                lost, at=int(it_end),
                checkpoint_step=self.last_checkpoint_step,
                step_offset=self.step_offset,
            )
        if self.preempt_at is not None and it_end >= self.preempt_at:
            self._flush_loggers()
            if self.ledger is not None:
                self.ledger.record("preemption", it=int(it_end))
            if self.recorder is not None:
                self.recorder.dump("preemption", extra={"it": int(it_end)})
            raise SimulatedPreemptionError(it_end)

    def after_step(self, it: int, metrics: Dict[str, Any],
                   state_fn: StateFn) -> None:
        self.after_superstep(it, 1, metrics, state_fn)

    def finish(self, state_fn: StateFn) -> None:
        """Flush the one-step-delayed watchdog — and any attached
        delayed loggers — after the loop ends (the watchdog may still
        raise, so loggers flush first)."""
        self._flush_loggers()
        self._check_pending(state_fn)
