#!/usr/bin/env python3
"""From the profiler's ``.xplane.pb`` to the numbers the layer metrics read.

    python benchmarks/reduce_trace.py <file.xplane.pb>          # the reduction, as JSON
    python benchmarks/reduce_trace.py --dump <file.xplane.pb>   # planes, lines, names: look first

What a chip trace holds (looked at by hand, PR 24, "TPU v5 lite"): one
plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` carries one
event per executed HLO instruction, a ``while`` (a scan) enclosing the ops
of its body on the same line; ``XLA Modules`` carries one event per
program run; the host's threads are lines of ``/host:CPU``, where
``jax.profiler.TraceAnnotation`` spans appear under their own names.  All
planes share one clock (nanoseconds from the start of the trace).

The window is the host span ``bench.window`` (harness.traced_window); the
device was synchronised before it opened and before it closed.

  busy_s       union of the device-op intervals inside the window, averaged
               over the device planes
  window_s     the window's length
  device_ops   [[name, seconds]] SELF time per op name (an enclosing
               ``while`` is charged only what its body does not cover),
               averaged over the planes, largest first
  kernel_s     self time of the Mosaic (Pallas) custom calls
  idle_gaps    [[host span, seconds]] idle time by the benchmark's span
               (``bench.*``) that covered the gap's middle, largest first

On a CPU (``--rehearse``) there is no device plane: the XLA:CPU client's
op events (those with an ``hlo_op`` stat) stand in, so that the path is
rehearsed; such a number is never a device number.
"""
from __future__ import annotations

import bisect
import json
import sys
import warnings
from collections import defaultdict

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
# an event of ``XLA Ops`` is named by its whole HLO instruction text,
# ``%name = shape op(operands), attributes``; a Mosaic (Pallas) kernel is
# the custom call with this target (``AllocateBuffer`` custom calls are not)
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def _stats(event) -> dict:
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return {str(k): v for k, v in event.stats}


def short(name: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``; a kernel
    keeps the mark that tells it from XLA's own ops."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return f"{head} (tpu_custom_call)" if KERNEL_MARK in name else head


def host_spans(profile):
    """Every ``bench.*`` span of the host plane: [(name, start_ns, end_ns)]."""
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return spans


def device_lines(profile):
    """[(plane name, [(name, start, end, is_kernel)])] of the device ops."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out.append((plane.name, [
                        (short(e.name), e.start_ns, e.start_ns + e.duration_ns,
                         KERNEL_MARK in e.name) for e in line.events]))
    if out:
        return out
    events = []  # CPU rehearsal: XLA:CPU op events of every client thread
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if "hlo_op" in _stats(e):
                        events.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                       False))
    return [("/host:CPU (rehearsal)", events)] if events else []


def union(intervals):
    """Merged, sorted intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def self_times(events):
    """{name: self ns} and kernel self ns: an event that encloses others on
    its line is charged its length less what they cover."""
    by_name, kernel = defaultdict(float), 0.0
    stack = []  # [name, end, self, is_kernel]

    def close(item):
        nonlocal kernel
        by_name[item[0]] += item[2]
        if item[3]:
            kernel += item[2]

    for name, start, end, mark in sorted(events, key=lambda e: (e[1], -(e[2] - e[1]))):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start, mark])
    while stack:
        close(stack.pop())
    return by_name, kernel


def reduce(path) -> dict:
    profile = load(path)
    spans = host_spans(profile)
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    _, w0, w1 = windows[-1]
    inner = sorted((s for s in spans if s[0] != WINDOW_SPAN and s[2] > w0 and s[1] < w1),
                   key=lambda s: s[1])
    starts = [s[1] for s in inner]

    def span_at(t):
        """The latest-started span that covers ``t`` (the innermost, where
        spans nest; a few spans back is as deep as the benchmark's go)."""
        last = bisect.bisect_right(starts, t) - 1
        for name, _start, end in reversed(inner[max(last - 7, 0):last + 1]):
            if end > t:
                return name
        return "(no bench span)"

    lines = device_lines(profile)
    if not lines:
        raise ValueError(f"no device ops in {path}")

    busy, kernel, ops, gaps = 0.0, 0.0, defaultdict(float), defaultdict(float)
    for _plane, events in lines:
        events = [(n, max(s, w0), min(e, w1), k) for n, s, e, k in events
                  if e > w0 and s < w1]
        merged = union((s, e) for _n, s, e, _k in events)
        busy += sum(e - s for s, e in merged)
        by_name, kernel_ns = self_times(events)
        kernel += kernel_ns
        for name, ns in by_name.items():
            ops[name] += ns
        edges = [w0] + [x for pair in merged for x in pair] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps[span_at((g0 + g1) / 2)] += g1 - g0
    n = len(lines)

    def ranked(table):
        return [[name, ns / n / 1e9] for name, ns in
                sorted(table.items(), key=lambda kv: -kv[1]) if ns > 0]

    return {"busy_s": busy / n / 1e9, "window_s": (w1 - w0) / 1e9,
            "kernel_s": kernel / n / 1e9, "device_planes": n,
            "device_ops": ranked(ops), "idle_gaps": ranked(gaps)}


def idle_share(trace):
    """Per cent of the traced window in which no device op ran."""
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def dump(path, top: int = 25) -> None:
    profile = load(path)
    for plane in profile.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            totals = defaultdict(lambda: [0, 0.0])
            for e in events:
                totals[e.name][0] += 1
                totals[e.name][1] += e.duration_ns
            for name, (count, ns) in sorted(totals.items(), key=lambda kv: -kv[1][1])[:top]:
                print(f"      {ns / 1e6:12.3f} ms  x{count:<7d} {name[:120]}")
            shown = 0
            for e in events:
                if shown < 3 or (KERNEL_MARK in e.name and shown < 6):
                    print("      SAMPLE", e.name[:300], e.start_ns, e.duration_ns,
                          {k: str(v)[:160] for k, v in _stats(e).items()})
                    shown += 1


if __name__ == "__main__":
    if sys.argv[1] == "--dump":
        dump(sys.argv[2])
    else:
        print(json.dumps(reduce(sys.argv[1]), indent=1))
