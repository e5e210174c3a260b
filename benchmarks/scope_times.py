"""Device time per layer of the FUSED train step, from the traced window.

A chip trace names an ``XLA Ops`` event by its instruction and carries no
op path (PR 24); the step program's text does.  The program hands out the
map ``{instruction name: (scope path, direction)}`` of the newest step
executable it compiled (``gymfx_tpu.telemetry.scopes.last_step_scope_map``,
fed by ``bench_util.compile_train_step``, which the train runner calls last
for the measured program), and this module joins it to the SELF time of
each op of the traced window as ``reduce_trace.reduce`` took it (the run's
``trace["device_ops"]``: the trace is not parsed a second time).  The
result is seconds per train step and scope path:

  rollout/env_step/tape_read   the layer an instruction was written in
  rollout, update              what a phase holds outside its layers (a
                               scan's bookkeeping, an op XLA left between)
  ""                           no scope at all (copies XLA adds, ...)

each split by direction: ``fwd`` under ``jvp(...)``, ``bwd`` under
``transpose(...)``, else ``""``.  Where XLA fuses across a boundary the
fusion is charged to the scope its metadata holds, its root's.

A program without ``telemetry/scopes.py`` (every commit before PR 26) has
no map: every reader here then returns ``None`` and the metric is left out
of the line.  On ``--rehearse`` the XLA:CPU client's op events stand in
(reduce_trace.device_lines); such a number is never a device number.

    python benchmarks/scope_times.py <file.xplane.pb> <scope_map.json> <steps>
"""
from __future__ import annotations

import json
import sys
from collections import defaultdict

import reduce_trace

NO_SCOPE = "(no scope)"
KERNEL_SUFFIX = " (tpu_custom_call)"  # reduce_trace.short's mark
_tables: dict = {}  # trace file -> table, so that thirteen readers build and print once


def op_seconds(path) -> dict:
    """{op name: self seconds inside the window}, averaged over the device
    planes: the ``device_ops`` of reduce_trace.reduce, as a dict."""
    return dict(reduce_trace.reduce(path)["device_ops"])


def build(ops: dict, scope_map: dict, steps: int) -> dict:
    """The table: seconds per train step by (scope path, direction), and
    by scope path the ops that make it up, largest first."""
    seconds, members = defaultdict(float), defaultdict(list)
    for name, s in ops.items():
        path, direction = scope_map.get(name.removesuffix(KERNEL_SUFFIX), ("", None))
        seconds[path, direction or ""] += s / steps
        members[path].append((name, s / steps))
    for ops_of in members.values():
        ops_of.sort(key=lambda kv: -kv[1])
    return {"seconds": dict(seconds), "ops": dict(members), "steps": steps,
            "busy_s": sum(ops.values()) / steps}


def total(table: dict, *prefixes: str, direction=None, last=None) -> float:
    """Seconds per step of every scope path at or under one of
    ``prefixes`` (or, with ``last``, ending in that name), in one
    direction if one is given."""
    def wanted(path, way):
        names = path.split("/")
        inside = (names[-1] == last) if last else any(
            names[:len(p.split("/"))] == p.split("/") for p in prefixes)
        return inside and direction in (None, way)

    return sum(s for (path, way), s in table["seconds"].items() if wanted(path, way))


def outside_phases(table: dict) -> float:
    """Seconds per step under neither phase: no scope, or a path cut off
    below a phase that the program could not root."""
    from gymfx_tpu.telemetry.scopes import PHASE_SCOPES

    return table["busy_s"] - total(table, *PHASE_SCOPES)


def in_a_layer(table: dict) -> float:
    """Seconds per step charged to a layer: under a phase, and not to a
    scope that only groups layers."""
    from gymfx_tpu.telemetry.scopes import GROUP_SCOPES, PHASE_SCOPES

    return total(table, *PHASE_SCOPES) - sum(
        s for (path, _way), s in table["seconds"].items() if path in GROUP_SCOPES)


def note(table: dict) -> dict:
    """What the ``scope_ms`` note line holds: milliseconds per train step
    by scope path (own time by direction, and with what lies under it),
    and the ten largest ops of each of the three largest scopes."""
    rows = defaultdict(dict)
    for (path, way), s in sorted(table["seconds"].items()):
        rows[path or NO_SCOPE][way or "own"] = 1e3 * s
    for path, row in rows.items():
        if path != NO_SCOPE:
            row["with_children"] = 1e3 * total(table, path)
    own = {path: sum(s for (p, _w), s in table["seconds"].items() if p == path)
           for path in table["ops"]}
    largest = sorted(own, key=own.get, reverse=True)[:3]
    return {
        "train_steps": table["steps"],
        "busy_ms_per_step": 1e3 * table["busy_s"],
        "outside_phases_ms": 1e3 * outside_phases(table),
        "scopes": dict(rows),
        "largest_ops": {path or NO_SCOPE: [[name, 1e3 * s] for name, s in
                                          table["ops"][path][:10]] for path in largest},
    }


def table_of(run):
    """The table of this run's traced window, or ``None`` where there is
    no trace or the program hands out no scope map.  Built once per trace
    file; the whole table goes on one note line then."""
    xplane = (run.get("trace") or {}).get("xplane")
    steps = run.get("counters", {}).get("train_steps")
    if not xplane or not steps:
        return None
    if xplane not in _tables:
        try:
            from gymfx_tpu.telemetry.scopes import last_step_scope_map
        except ImportError:  # a program from before PR 26
            scope_map = None
        else:
            scope_map = last_step_scope_map()
        _tables[xplane] = None
        if scope_map:
            import harness

            _tables[xplane] = build(dict(run["trace"]["device_ops"]), scope_map, steps)
            harness.note(scope_ms=note(_tables[xplane]))
    return _tables[xplane]


def ms(run, *prefixes, direction=None, last=None):
    """A layer metric: device milliseconds per train step (see total)."""
    table = table_of(run)
    if table is None:
        return None
    return 1e3 * total(table, *prefixes, direction=direction, last=last)


if __name__ == "__main__":
    scope_map = {k: tuple(v) for k, v in json.load(open(sys.argv[2])).items()}
    print(json.dumps(note(build(op_seconds(sys.argv[1]), scope_map, int(sys.argv[3]))),
                     indent=1))
