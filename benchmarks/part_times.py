"""Device time per PART of a block of the fused train step (PR 37).

``scope_times.py`` reads the LAYER map: one path per instruction, each
ending in a layer of ``gymfx_tpu/telemetry/scopes.py``'s vocabulary.  Inside
a layer the program plants parts that vocabulary leaves out -- ``kda_scan``
(the gated-delta-rule scan, both directions), ``causal_conv`` (the taps of a
short convolution) and ``attention_core`` (head norms, rotation, layout and
the attention call, between the products) -- and hands out a second map, the
PART map (``scopes.last_step_part_map``), built from the same text: the same
paths with the part behind the layer where an op lies in one, and the copies
and async pairs XLA added without metadata named after their users.  This
module joins that map to the traced window's ops through
``scope_times.build`` / ``total``, as scope_times joins the layer map:

  update/loss/policy_forward/linear_attention/kda_scan   fwd / bwd
  rollout/policy_act/attention/attention_core            ""
  ""                                                     what it cannot name

and writes one ``part_ms`` note line per trace: ms per train step of every
part path by direction, the whole table's sum beside the busy time, what is
left unnamed with its ten largest ops and their opcodes, and the host seconds
it took to build the two maps.  A program without a part map (every commit
before PR 37) reads ``None`` everywhere here.
"""
from __future__ import annotations

from collections import defaultdict

import scope_times

_tables: dict = {}  # trace file -> table, so that four readers build and print once


def _program():
    """(part map, unnamed opcodes, build cost) of the newest step, or
    ``None`` for a program that hands out no part map."""
    try:
        from gymfx_tpu.telemetry.scopes import (
            build_cost,
            last_step_part_map,
            last_step_unnamed,
        )
    except ImportError:  # a program from before PR 37
        return None
    part_map = last_step_part_map()
    if not part_map:
        return None
    return part_map, last_step_unnamed() or {}, dict(build_cost)


def unnamed(table: dict) -> float:
    """Seconds per step the part map leaves with no path."""
    return sum(s for (path, _way), s in table["seconds"].items() if not path)


def note(table: dict, opcodes: dict, build_cost: dict) -> dict:
    """What the ``part_ms`` note line holds."""
    from gymfx_tpu.telemetry.scopes import PART_NAMES

    parts = defaultdict(dict)
    for (path, way), s in sorted(table["seconds"].items()):
        if path.split("/")[-1] in PART_NAMES:
            parts[path][way or "own"] = 1e3 * s
    return {
        "train_steps": table["steps"],
        "busy_ms_per_step": 1e3 * table["busy_s"],
        "all_paths_ms": 1e3 * sum(table["seconds"].values()),
        "parts": dict(parts),
        "unnamed_ms": 1e3 * unnamed(table),
        "largest_unnamed": [
            [name, opcodes.get(name.removesuffix(scope_times.KERNEL_SUFFIX), "?"), 1e3 * s]
            for name, s in table["ops"].get("", [])[:10]],
        "build": build_cost,
    }


def table_of(run):
    """The part table of this run's traced window, or ``None`` where there is
    no trace or the program hands out no part map."""
    xplane = (run.get("trace") or {}).get("xplane")
    steps = run.get("counters", {}).get("train_steps")
    if not xplane or not steps:
        return None
    if xplane not in _tables:
        _tables[xplane] = None
        program = _program()
        if program:
            import harness

            part_map, opcodes, build_cost = program
            _tables[xplane] = scope_times.build(
                dict(run["trace"]["device_ops"]), part_map, steps)
            harness.note(part_ms=note(_tables[xplane], opcodes, build_cost))
    return _tables[xplane]


def ms(run, *prefixes, direction=None, last=None):
    """A part metric: device milliseconds per train step (scope_times.total)."""
    table = table_of(run)
    if table is None:
        return None
    return 1e3 * scope_times.total(table, *prefixes, direction=direction, last=last)
