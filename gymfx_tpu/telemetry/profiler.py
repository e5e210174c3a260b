"""Managed jax.profiler trace capture: superstep-windowed, manifested,
never-raises.

Raw ``jax.profiler.trace`` dumps (the old ``bench.py --trace`` path)
leave an anonymous directory nobody can attribute later.  :class:`ProfilerSession` owns the capture
instead: it starts/stops the trace around a superstep dispatch window
on a configured cadence and writes a **capture bundle** —

  ``capture_NNN_itM/``
    ``plugins/profile/<ts>/*.trace.json.gz``  (what jax.profiler wrote)
    ``manifest.json``   provenance: config sha256, superstep range,
                        platform/device_kind/comparable triple,
                        compile-watch executable fingerprints, and the
                        workload payload (XLA/analytic FLOPs, the
                        ``bench_util.measure_phase_split`` baseline)
    ``scope_map.json``  op name -> rollout/update scope, recovered from
                        the compiled executable's optimized-HLO
                        ``op_name`` metadata (trace_parse.py) — CPU
                        trace events carry no scope info, so this
                        sidecar is what keeps attribution tier-1
                        testable

and ledgers a ``profile_capture`` event.  ``tools/profile_report.py``
turns a bundle into the schema-pinned ``profile_report.json``
(attribution.py).

Config knobs (defaults.py, all off; built by ``telemetry_from_config``):

  ``telemetry_profile_dir``        capture bundle directory (the master
                                   switch — unset = sessions are never
                                   constructed, fast paths untouched)
  ``telemetry_profile_supersteps`` comma-separated superstep indices to
                                   capture ("1" or "1,8"); default "1"
                                   (the first post-warmup dispatch —
                                   superstep 0's window contains the
                                   jit compile)
  ``telemetry_profile_every``      cadence: capture every Nth superstep
                                   (0 = off)

Cost model: a due capture adds ONE device sync (the trainer blocks the
dispatch so the trace covers it) plus, at bundle-write time, one AOT
recompile of the dispatched program (for the scope map + cost model)
and the two phase-split sub-programs on a copy of the live state —
seconds on CPU CI shapes, tens of seconds at TPU flagship shapes, paid
only on capture supersteps.  Everything is wrapped in the telemetry
never-raises discipline: failures land in ``capture_errors``, never in
the training loop.
"""
from __future__ import annotations

import json
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, Iterable, Optional, Sequence, Union

from gymfx_tpu.telemetry.scopes import PHASE_SCOPES

MANIFEST_NAME = "manifest.json"
SCOPE_MAP_NAME = "scope_map.json"
CAPTURE_MANIFEST_VERSION = 1


def _parse_supersteps(raw: Union[None, int, str, Iterable[int]]
                      ) -> Optional[tuple]:
    """Normalize the ``telemetry_profile_supersteps`` knob: int, list,
    or comma-separated string -> sorted tuple of superstep indices."""
    if raw is None or raw == "" or raw is False:
        return None
    if isinstance(raw, bool):
        return None
    if isinstance(raw, int):
        return (int(raw),)
    if isinstance(raw, (list, tuple, set)):
        return tuple(sorted(int(v) for v in raw))
    return tuple(sorted(
        int(tok) for tok in str(raw).split(",") if tok.strip()
    ))


class _Capture:
    """Context manager returned by :meth:`ProfilerSession.capture`."""

    def __init__(self, session: "ProfilerSession", it_start: int, k: int,
                 label: str):
        self.session = session
        self.it_start = int(it_start)
        self.k = int(k)
        self.label = label
        self.bundle: Optional[str] = None

    def __enter__(self) -> "_Capture":
        self.session.start_capture(
            self.it_start, self.k, label=self.label, force=True
        )
        return self

    def __exit__(self, *exc: Any) -> None:
        self.bundle = self.session.finish_capture()


class ProfilerSession:
    """Cadence-gated jax.profiler capture windows with manifested
    bundles; every public method is never-raises."""

    def __init__(
        self,
        out_dir: str,
        *,
        supersteps: Union[None, int, str, Iterable[int]] = None,
        every: int = 0,
        config_sha256: Optional[str] = None,
        registry: Any = None,
        ledger: Any = None,
        compile_watch: Any = None,
        scopes: Sequence[str] = PHASE_SCOPES,
    ):
        self.out_dir = Path(out_dir)
        self.supersteps = _parse_supersteps(supersteps)
        self.every = int(every or 0)
        if self.supersteps is None and self.every <= 0:
            # dir configured but no cadence: one capture at superstep 1,
            # the first dispatch whose window holds no jit compile
            self.supersteps = (1,)
        self.config_sha256 = config_sha256
        self.ledger = ledger
        self.compile_watch = compile_watch
        self.scopes = tuple(scopes)
        self._workload_source: Optional[Callable[[int, int], Any]] = None
        self._lock = threading.Lock()
        self._capture_seq = 0
        self._active: Optional[Dict[str, Any]] = None
        self._last_capture_ts: Optional[float] = None
        self.captures = 0
        self.capture_errors = 0
        self._counter = None
        if registry is not None:
            try:
                self._counter = registry.counter(
                    "gymfx_profile_captures_total",
                    "Completed profiler trace captures",
                )
                registry.gauge(
                    "gymfx_profile_last_capture_age_seconds",
                    "Seconds since the last completed profiler capture "
                    "(-1 before the first)",
                ).set_function(self._last_capture_age)
            except Exception:
                self._counter = None

    # ------------------------------------------------------------------
    def _last_capture_age(self) -> float:
        ts = self._last_capture_ts
        return -1.0 if ts is None else max(0.0, time.time() - ts)

    def set_workload_source(self, fn: Callable[[int, int], Any]) -> None:
        """Bind a ``fn(it_start, k) -> dict`` resolved at bundle-write
        time (after the trace stopped, outside the capture window).
        The dict is merged into the manifest; the special key
        ``hlo_text`` (the dispatched program's optimized HLO) is parsed
        into the ``scope_map.json`` sidecar instead of stored."""
        self._workload_source = fn

    def due(self, it_start: int, k: int = 1) -> bool:
        """True when the dispatch window ``[it_start, it_start + k)``
        contains a configured capture superstep (explicit list, or a
        multiple of ``every``)."""
        try:
            it_start, k = int(it_start), max(1, int(k))
        except Exception:
            return False
        if self.supersteps is not None and any(
                it_start <= t < it_start + k for t in self.supersteps):
            return True
        if self.every > 0:
            first = ((it_start + self.every - 1) // self.every) * self.every
            if it_start <= first < it_start + k:
                return True
        return False

    @property
    def capturing(self) -> bool:
        return self._active is not None

    # ------------------------------------------------------------------
    def start_capture(self, it_start: int, k: int = 1, *,
                      label: str = "superstep", force: bool = False) -> bool:
        """Start tracing the window when due (or ``force``); returns
        whether a capture is now open.  The caller must block the
        dispatch result before :meth:`finish_capture` so the trace
        covers the device work."""
        try:
            if self._active is not None:
                return False
            if not force and not self.due(it_start, k):
                return False
            with self._lock:
                self._capture_seq += 1
                seq = self._capture_seq
            bundle = self.out_dir / f"capture_{seq:03d}_it{int(it_start)}"
            bundle.mkdir(parents=True, exist_ok=True)
            import jax

            jax.profiler.start_trace(str(bundle))
            self._active = {
                "bundle": bundle,
                "it_start": int(it_start),
                "k": max(1, int(k)),
                "label": str(label),
                "seq": seq,
                "t0": time.time(),
            }
            return True
        except Exception:
            self.capture_errors += 1
            self._active = None
            return False

    def finish_capture(self) -> Optional[str]:
        """Stop the open trace and write the bundle (manifest, scope
        map, ledger event, counter tick); returns the bundle path, or
        None when no capture was open / the write failed."""
        active = self._active
        if active is None:
            return None
        self._active = None
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception:
            self.capture_errors += 1
            return None
        try:
            return self._write_bundle(active)
        except Exception:
            self.capture_errors += 1
            return None

    def capture(self, *, it_start: int = 0, k: int = 1,
                label: str = "manual") -> _Capture:
        """One-shot context manager for the bench tools (ignores the
        cadence knobs).  The body must block its device work before
        exiting so the trace covers it."""
        return _Capture(self, it_start, k, label)

    def close(self) -> None:
        """Finalize a capture left open by an aborted loop
        (idempotent)."""
        self.finish_capture()

    # ------------------------------------------------------------------
    def _write_bundle(self, active: Dict[str, Any]) -> Optional[str]:
        from gymfx_tpu.telemetry.flight_recorder import _jsonable

        bundle: Path = active["bundle"]
        it_start, k = active["it_start"], active["k"]
        manifest: Dict[str, Any] = {
            "schema_version": CAPTURE_MANIFEST_VERSION,
            "ts": time.time(),
            "label": active["label"],
            "seq": active["seq"],
            "config_sha256": self.config_sha256,
            "it_start": it_start,
            "k": k,
            "it_end": it_start + k,
            "capture_wall_s": time.time() - active["t0"],
        }
        try:
            import jax

            from gymfx_tpu.bench_util import (
                device_peak_flops,
                stamp_comparability,
            )

            device = jax.local_devices()[0]
            stamp_comparability(manifest, device=device)
            manifest["hw_flops_peak"] = device_peak_flops(device)
        except Exception:
            manifest.setdefault("platform", "unknown")
            manifest.setdefault("device_kind", "unknown")
            manifest.setdefault("comparable", False)
            manifest.setdefault("hw_flops_peak", None)
        info: Dict[str, Any] = {}
        if self._workload_source is not None:
            try:
                info = dict(self._workload_source(it_start, k) or {})
            except Exception:
                manifest["workload_error"] = True
        hlo_text = info.pop("hlo_text", None)
        if hlo_text:
            try:
                from gymfx_tpu.telemetry.scopes import scope_map_from_hlo

                scope_map = {
                    name: scope.path for name, scope in
                    scope_map_from_hlo(hlo_text, scopes=self.scopes).items()
                }
                if scope_map:
                    (bundle / SCOPE_MAP_NAME).write_text(
                        json.dumps(scope_map), encoding="utf-8"
                    )
                    manifest["scope_map_file"] = SCOPE_MAP_NAME
                    manifest["scope_map_ops"] = len(scope_map)
            except Exception:
                pass
            try:
                import hashlib

                sha = hashlib.sha256(
                    hlo_text.encode("utf-8", errors="replace")
                ).hexdigest()
                manifest["hlo_sha256"] = sha
                if self.compile_watch is not None:
                    # register the captured program's identity so it
                    # shows up in the fingerprint table below (training
                    # compiles arrive via jax.monitoring without one)
                    self.compile_watch.record_compile(
                        f"profile:{active['label']}",
                        key=f"it{it_start}", hlo_sha256=sha,
                    )
            except Exception:
                pass
        if self.compile_watch is not None:
            try:
                manifest["fingerprints"] = self.compile_watch.fingerprints()
            except Exception:
                manifest["fingerprints"] = {}
        else:
            manifest["fingerprints"] = {}
        for key, value in info.items():
            manifest.setdefault(str(key), _jsonable(value))
        with open(bundle / MANIFEST_NAME, "w", encoding="utf-8") as fh:
            json.dump(_jsonable(manifest), fh, indent=2, sort_keys=True)
            fh.write("\n")
        self._last_capture_ts = time.time()
        with self._lock:
            self.captures += 1
        if self._counter is not None:
            try:
                self._counter.inc()
            except Exception:
                pass
        if self.ledger is not None:
            self.ledger.record(
                "profile_capture", path=str(bundle),
                it_start=int(it_start), k=int(k),
            )
        return str(bundle)


def find_captures(root: str) -> list:
    """Manifested capture bundles under ``root`` (itself a bundle, a
    session dir, or any ancestor), oldest first."""
    try:
        base = Path(root)
        if (base / MANIFEST_NAME).exists():
            return [str(base)]
        return sorted(
            str(p.parent) for p in base.rglob(MANIFEST_NAME)
        )
    except Exception:
        return []
