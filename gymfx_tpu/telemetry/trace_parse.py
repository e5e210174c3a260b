"""Stdlib-only parser for the perfetto ``trace.json.gz`` jax.profiler
emits — the reading half of the performance observatory.

``bench.py --trace`` and :class:`~gymfx_tpu.telemetry.profiler.ProfilerSession`
write Chrome-trace JSON under ``<dir>/plugins/profile/<ts>/``; nothing
in the repo read it back until this module.  :func:`parse_trace` turns
one capture into an aggregate summary: device vs host lanes, per-op
duration totals, the device-busy interval union and the dispatch-gap
window — everything :mod:`gymfx_tpu.telemetry.attribution` needs to
attribute measured device time.

Lane splitting: an "X" (complete) event is DEVICE work when its args
carry the XLA op identity (``hlo_op``/``hlo_module`` — how the CPU
backend's executor threads report) or when its process is a
``/device:``-named lane (how TPU device streams report); everything
else is host-side (python dispatch, ``TraceAnnotation`` spans).

Scope grouping: TPU device events often carry the full
``jit(...)/rollout/...`` op path in their args; CPU thunk events carry
only the bare HLO instruction name.  ``scopes.scope_map_from_hlo``
recovers the mapping from the compiled executable's optimized-HLO
``op_name`` metadata (where the ``jax.named_scope`` annotations the
trainers plant survive compilation), and the profiler stores it as a ``scope_map.json`` sidecar in the capture
bundle so grouping works on any backend.

Never-raises contract: a malformed capture yields ``ok=False`` and an
empty summary — a broken trace costs the report, never the caller.
"""
from __future__ import annotations

import gzip
import json
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

# the scope vocabulary and the HLO scope map live in scopes.py; this
# module keeps the perfetto half
from gymfx_tpu.telemetry.scopes import PHASE_SCOPES


def find_trace_files(root: str) -> List[str]:
    """Every ``*.trace.json(.gz)`` under ``root`` (a capture bundle or
    a raw ``jax.profiler`` output dir), sorted for determinism."""
    try:
        base = Path(root)
        if base.is_file():
            return [str(base)]
        out = sorted(
            str(p) for pattern in ("*.trace.json.gz", "*.trace.json")
            for p in base.rglob(pattern)
        )
        return out
    except Exception:
        return []


def _load_events(path: str) -> List[Dict[str, Any]]:
    raw = Path(path).read_bytes()
    if raw[:2] == b"\x1f\x8b":
        raw = gzip.decompress(raw)
    doc = json.loads(raw.decode("utf-8", errors="replace"))
    events = doc.get("traceEvents", []) if isinstance(doc, dict) else []
    return [e for e in events if isinstance(e, dict)]


def _scope_from_path(path: str,
                     scopes: Sequence[str]) -> Optional[str]:
    """First ``scopes`` member on an ``op_name`` path ("jit(main)/
    rollout/while/..." -> "rollout"), or None."""
    for part in str(path).split("/"):
        if part in scopes:
            return part
    return None


def _merged_span_us(intervals: List[Tuple[float, float]]) -> float:
    """Total covered microseconds of the interval union (device lanes
    can overlap across executor threads; a plain sum double-counts)."""
    total = 0.0
    end = None
    for start, stop in sorted(intervals):
        if end is None or start > end:
            total += stop - start
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def _empty_summary(error: Optional[str] = None) -> Dict[str, Any]:
    return {
        "ok": error is None,
        "error": error,
        "trace_files": [],
        "events": 0,
        "device_lanes": [],
        "host_lanes": [],
        "device_total_us": 0.0,
        "device_busy_us": 0.0,
        "window_us": 0.0,
        "host_total_us": 0.0,
        "ops": {},
        "host_ops": {},
    }


def parse_trace(root: str,
                scopes: Sequence[str] = PHASE_SCOPES) -> Dict[str, Any]:
    """Aggregate one capture (bundle dir, profiler output dir, or a
    single trace file) into a summary dict; never raises.

    ``ops`` maps device op name -> ``{count, total_us, module, path,
    scope}`` (``path``/``scope`` filled when the event args carried the
    op path — TPU traces); ``host_ops`` is the same aggregation over
    host-lane events (python dispatch frames, ``TraceAnnotation``
    spans like ``train/superstep``).

    Device op totals are SELF time (duration minus contained child
    events on the same thread): the CPU executor emits a ``while``
    loop thunk as one long event *containing* its body thunks, so raw
    durations double-count every nested op and skew attribution —
    self times partition the busy time instead."""
    try:
        files = find_trace_files(root)
        if not files:
            return _empty_summary(f"no trace files under {root!r}")
        processes: Dict[Any, str] = {}
        threads: Dict[Tuple[Any, Any], str] = {}
        ops: Dict[str, Dict[str, Any]] = {}
        host_ops: Dict[str, Dict[str, Any]] = {}
        device_lanes: Dict[str, float] = {}
        host_lanes: Dict[str, float] = {}
        device_intervals: List[Tuple[float, float]] = []
        # (file, pid, tid) -> [[ts, dur, name, lane, args], ...] so the
        # self-time pass can detect nesting per thread
        lane_events: Dict[Tuple[Any, Any, Any], List[list]] = {}
        n_events = 0
        parsed_any = False
        for path in files:
            try:
                events = _load_events(path)
            except Exception:
                continue
            parsed_any = True
            # metadata pass first: lane names may be declared after use
            for ev in events:
                if ev.get("ph") != "M":
                    continue
                args = ev.get("args") or {}
                if ev.get("name") == "process_name":
                    processes[ev.get("pid")] = str(args.get("name", ""))
                elif ev.get("name") == "thread_name":
                    threads[(ev.get("pid"), ev.get("tid"))] = str(
                        args.get("name", "")
                    )
            for ev in events:
                if ev.get("ph") != "X":
                    continue
                n_events += 1
                args = ev.get("args") or {}
                pid, tid = ev.get("pid"), ev.get("tid")
                pname = processes.get(pid, str(pid))
                lane = f"{pname}/{threads.get((pid, tid), str(tid))}"
                name = str(ev.get("name", "?"))
                try:
                    ts = float(ev.get("ts", 0.0))
                    dur = float(ev.get("dur", 0.0))
                except Exception:
                    ts, dur = 0.0, 0.0
                is_device = (
                    "hlo_op" in args or "hlo_module" in args
                    or pname.startswith("/device:")
                )
                if is_device:
                    lane_events.setdefault((path, pid, tid), []).append(
                        [ts, dur, name, lane, args]
                    )
                    device_intervals.append((ts, ts + dur))
                else:
                    hop = host_ops.setdefault(
                        name, {"count": 0, "total_us": 0.0}
                    )
                    hop["count"] += 1
                    hop["total_us"] += dur
                    host_lanes[lane] = host_lanes.get(lane, 0.0) + dur
        if not parsed_any:
            return _empty_summary(f"unparseable trace files under {root!r}")
        # self-time pass: per thread, subtract each event's directly
        # contained children so a container thunk (the rollout `while`)
        # keeps only its loop overhead and the body ops keep their own
        for events_list in lane_events.values():
            events_list.sort(key=lambda e: (e[0], -e[1]))
            stack: List[list] = []  # [end, child_dur_accumulator]
            for ev in events_list:
                ts, dur = ev[0], ev[1]
                while stack and stack[-1][0] <= ts:
                    stack.pop()
                if stack:
                    stack[-1][1] += dur
                frame = [ts + dur, 0.0]
                stack.append(frame)
                ev.append(frame)  # read child_dur after the walk
            for ts, dur, name, lane, args, frame in events_list:
                self_us = max(0.0, dur - frame[1])
                op = ops.setdefault(
                    name,
                    {"count": 0, "total_us": 0.0, "module": None,
                     "path": None, "scope": None},
                )
                op["count"] += 1
                op["total_us"] += self_us
                if op["module"] is None and args.get("hlo_module"):
                    op["module"] = str(args["hlo_module"])
                if op["path"] is None:
                    # TPU traces carry the op path in args; take the
                    # first arg value that looks like one
                    for key in ("long_name", "tf_op", "name"):
                        value = args.get(key)
                        if isinstance(value, str) and "/" in value:
                            op["path"] = value
                            op["scope"] = _scope_from_path(value, scopes)
                            break
                device_lanes[lane] = device_lanes.get(lane, 0.0) + self_us
        window = 0.0
        if device_intervals:
            window = (max(stop for _, stop in device_intervals)
                      - min(start for start, _ in device_intervals))
        return {
            "ok": True,
            "error": None,
            "trace_files": files,
            "events": n_events,
            "device_lanes": sorted(device_lanes),
            "host_lanes": sorted(host_lanes),
            "device_total_us": sum(op["total_us"] for op in ops.values()),
            "device_busy_us": _merged_span_us(device_intervals),
            "window_us": window,
            "host_total_us": sum(op["total_us"] for op in host_ops.values()),
            "ops": ops,
            "host_ops": host_ops,
        }
    except Exception as exc:  # the never-raises floor
        return _empty_summary(f"trace parse failed: {exc!r}")


def group_by_scope(summary: Dict[str, Any],
                   scope_map: Optional[Dict[str, str]] = None,
                   scopes: Sequence[str] = PHASE_SCOPES) -> Dict[str, float]:
    """Device time (us) per named scope: ``{scope: us, ...,
    "unattributed": us}``.  Attribution order per op: the scope the
    parser found in the event args (TPU), then the ``scope_map``
    sidecar lookup by op name (CPU), else unattributed."""
    groups: Dict[str, float] = {scope: 0.0 for scope in scopes}
    groups["unattributed"] = 0.0
    scope_map = scope_map or {}
    try:
        for name, op in (summary.get("ops") or {}).items():
            scope = op.get("scope")
            if scope not in scopes:
                mapped = scope_map.get(name)
                if mapped is not None and mapped not in scopes:
                    mapped = _scope_from_path(mapped, scopes)
                scope = mapped
            if scope in scopes:
                groups[scope] += float(op.get("total_us", 0.0))
            else:
                groups["unattributed"] += float(op.get("total_us", 0.0))
    except Exception:
        pass
    return groups
