#!/usr/bin/env python3
"""Pod-scale elastic chaos: train on a virtual mesh, kill a device
mid-run via the ``mesh=`` fault grammar (docs/resilience.md, "Elastic
training"), and prove the run survives — mesh re-planned over the
survivors, last digest-verified checkpoint re-entered against the new
plan, zero supersteps lost past that checkpoint.

The drill runs PPO on a CPU virtual mesh (``--xla_force_host_platform
_device_count``, the same mechanism the sharded-runtime tests use):

  1. train on ``{"data": 4}`` with periodic checkpoints and
     ``mesh=kill:<device>@<superstep>`` armed — the resilient loop
     ledgers ``mesh_degrade``, dumps the flight recorder and raises
     DeviceLossError at the scripted boundary;
  2. the elastic controller (parallel/elastic.py run_elastic) re-plans
     to the survivor shape — 3 survivors repartition to ``{"data": 2}``
     because 16 envs don't divide 3 — excludes the dead device, and
     resumes from the last checkpoint through the digest-verified
     restore path;
  3. the WHOLE chaos run is then replayed in a fresh workdir: final
     policy params must come back bitwise identical (deterministic
     replay — the elastic path added no hidden nondeterminism).

Pass bars (the report's ``passed``): at least one degrade AND one
verified resume, zero supersteps lost past the last checkpoint, a
stream-preserving repartition, a postmortem bundle on disk, every
per-attempt ledger schema-valid, and bitwise replay parity.

The run emits a schema-pinned ``elastic_report.json``
(tools/elastic_report_schema.json):

    python tools/elastic_chaos.py --quick
    python tools/elastic_chaos.py --quick \\
        --fault_profile 'mesh=kill:3@2'

``validate_elastic_report`` is imported by tests/test_elastic_chaos.py,
the tools/run_tests.sh elastic-chaos leg and tools/bench_sentinel.py
``--elastic-report``, keeping the schema and this emitter from drifting
apart silently.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

SCHEMA_PATH = Path(__file__).resolve().parent / "elastic_report_schema.json"

DEFAULT_FAULT_PROFILE = "mesh=kill:3@2"

VIRTUAL_DEVICES = 4

# the sub-minute CI shape: a tiny MLP policy on a 4-device virtual
# mesh, 16 envs (4 per shard), checkpoints every superstep so the
# zero-lost-work bar is exact
QUICK_CONFIG = {
    "input_data_file": "examples/data/eurusd_uptrend.csv",
    "window_size": 8,
    "num_envs": 16,
    "policy": "mlp",
    "policy_kwargs": {"hidden": (16,)},
    "ppo_horizon": 8,
    "ppo_epochs": 1,
    "ppo_minibatches": 2,
    "train_total_steps": 16 * 8 * 4,  # 4 iterations
    "checkpoint_every": 1,
    "mesh_shape": {"data": 4},
    "elastic_resume": True,
    "elastic_max_retries": 2,
    "elastic_shrink_policy": "repartition",
    "seed": 1,
    "quiet_mode": True,
}


def load_schema() -> Dict[str, Any]:
    with open(SCHEMA_PATH, encoding="utf-8") as fh:
        schema = json.load(fh)
    schema.pop("_comment", None)
    return schema


def validate_elastic_report(report: Dict[str, Any],
                            schema: Optional[Dict[str, Any]] = None,
                            ) -> List[str]:
    """Return a list of contract violations (empty = report conforms)."""
    if schema is None:
        schema = load_schema()
    if not isinstance(report, dict):
        return [f"report is not a JSON object: {type(report).__name__}"]
    problems: List[str] = []
    if report.get("kind") != schema["kind"]:
        problems.append(
            f"kind must be {schema['kind']!r}, got {report.get('kind')!r}"
        )
    for key in schema["required"]:
        if key not in report:
            problems.append(f"missing required key {key!r}")
    for key in schema["integer"]:
        if key in report and not (
            isinstance(report[key], int) and not isinstance(report[key], bool)
        ):
            problems.append(
                f"key {key!r} must be an integer, got {report[key]!r}"
            )
    for key in schema["numeric"]:
        if key in report and not (
            isinstance(report[key], (int, float))
            and not isinstance(report[key], bool)
            and math.isfinite(float(report[key]))
        ):
            problems.append(
                f"key {key!r} must be a finite number, got {report[key]!r}"
            )
    for key in schema["boolean"]:
        if key in report and not isinstance(report[key], bool):
            problems.append(
                f"key {key!r} must be a boolean, got {report[key]!r}"
            )
    for key in schema["object"]:
        if key in report and not isinstance(report[key], dict):
            problems.append(
                f"key {key!r} must be a JSON object, got {report[key]!r}"
            )
    return problems


def _params_bytes(checkpoint_dir: str) -> bytes:
    """Concatenated raw bytes of every params leaf in the newest
    checkpoint, in canonical leaf order — the replay-parity digest
    input (bitwise, not approximate)."""
    import jax
    import numpy as np

    from gymfx_tpu.train.checkpoint import load_params

    params, _step = load_params(checkpoint_dir)
    leaves = jax.tree.leaves(params)
    return b"".join(np.ascontiguousarray(leaf).tobytes() for leaf in leaves)


def _one_chaos_run(config: Dict[str, Any], workdir: Path,
                   fault_profile: str) -> Dict[str, Any]:
    """One full elastic chaos pass in ``workdir``; returns the trainer
    summary (with its ``elastic`` audit block on a resumed run)."""
    from gymfx_tpu.train.ppo import train_from_config

    cfg = dict(config)
    cfg["fault_profile"] = fault_profile
    cfg["checkpoint_dir"] = str(workdir / "ckpt")
    cfg["telemetry_ledger"] = str(workdir / "ledger.jsonl")
    cfg["telemetry_flight_recorder_dir"] = str(workdir / "postmortem")
    return train_from_config(cfg)


def run_elastic_chaos(
    config: Dict[str, Any],
    *,
    fault_profile: str = DEFAULT_FAULT_PROFILE,
    workdir: str,
    out: Optional[str] = None,
) -> Dict[str, Any]:
    """Run the chaos pass plus its deterministic replay and return (and
    optionally write) the schema-pinned report."""
    from gymfx_tpu.parallel.elastic import stream_preserving
    from gymfx_tpu.telemetry.ledger import read_ledger, validate_ledger

    t_start = time.perf_counter()
    workdir_p = Path(workdir)
    run_a = workdir_p / "run_a"
    run_b = workdir_p / "run_b"
    for d in (run_a, run_b):
        d.mkdir(parents=True, exist_ok=True)

    steps_per_iter = (
        int(config.get("num_envs", 16)) * int(config.get("ppo_horizon", 8))
    )
    summary = _one_chaos_run(config, run_a, fault_profile)
    elastic = summary.get("elastic") or {}
    history = elastic.get("degrades") or []
    attempts = int(elastic.get("attempts", 0) or 0)

    # -- ledger forensics: attempt-0 carries mesh_degrade, each retry's
    # per-attempt file carries checkpoint_restore + mesh_resume
    ledger_rows = 0
    ledger_problems: List[str] = []
    degrade_rows: List[Dict[str, Any]] = []
    resume_rows: List[Dict[str, Any]] = []
    ledgers = sorted(run_a.glob("ledger*.jsonl"))
    for path in ledgers:
        rows = read_ledger(str(path))
        ledger_rows += len(rows)
        ledger_problems += [
            f"{path.name}: {p}" for p in validate_ledger(str(path))
        ]
        degrade_rows += [r for r in rows if r.get("kind") == "mesh_degrade"]
        resume_rows += [r for r in rows if r.get("kind") == "mesh_resume"]

    checkpoint_step = -1
    resume_step = -1
    lost_supersteps = -1
    if degrade_rows:
        first = degrade_rows[0]
        checkpoint_step = int(first.get("checkpoint_step") or 0)
        degrade_at = int(first.get("at") or 0)
        lost_supersteps = degrade_at - checkpoint_step // steps_per_iter
    if resume_rows:
        resume_step = int(resume_rows[0].get("step") or 0)
        if checkpoint_step >= 0:
            # the resume must re-enter AT the last good checkpoint — any
            # gap is work lost past it
            lost_supersteps = (
                (checkpoint_step - resume_step) // steps_per_iter
                + max(0, lost_supersteps)
            )

    mesh_before = dict(
        (history[0].get("mesh_shape") and config.get("mesh_shape")) or
        config.get("mesh_shape") or {}
    ) if history else dict(config.get("mesh_shape") or {})
    mesh_after = dict(
        (elastic.get("mesh_shape") or summary.get("mesh_shape")) or {}
    )
    preserved = bool(history) and all(
        bool(h.get("stream_preserving")) for h in history
    ) and stream_preserving(mesh_before, mesh_after)

    postmortems = list((run_a / "postmortem").glob("**/manifest.json"))

    # -- deterministic replay: the identical chaos run in a fresh
    # workdir must land bitwise-identical final params
    _one_chaos_run(config, run_b, fault_profile)
    replay_parity = (
        _params_bytes(str(run_a / "ckpt")) ==
        _params_bytes(str(run_b / "ckpt"))
    )

    import numpy as np

    devices_before = int(np.prod(list(mesh_before.values()))) \
        if mesh_before else 0
    devices_after = int(np.prod(list(mesh_after.values()))) \
        if mesh_after else 0
    dead = len(elastic.get("lost_devices") or [])

    report = {
        "kind": "elastic_report",
        "schema_version": 1,
        "fault_profile": str(fault_profile),
        "mesh_before": mesh_before,
        "mesh_after": mesh_after,
        "devices_before": devices_before,
        "devices_after": devices_after,
        "attempts": attempts,
        "degrades": len(degrade_rows),
        "resumes": len(resume_rows),
        "dead_devices": dead,
        "checkpoint_step": checkpoint_step,
        "resume_step": resume_step,
        "lost_supersteps_past_checkpoint": int(lost_supersteps),
        "stream_preserving": bool(preserved),
        "postmortem_dumped": bool(postmortems),
        "ledger_rows": int(ledger_rows),
        "ledger_valid": not ledger_problems,
        "replay_parity": bool(replay_parity),
        "wall_s": float(time.perf_counter() - t_start),
        "passed": bool(
            attempts >= 1
            and degrade_rows
            and resume_rows
            and all(bool(r.get("verified")) for r in resume_rows)
            and lost_supersteps == 0
            and preserved
            and postmortems
            and not ledger_problems
            and replay_parity
        ),
    }
    if out:
        Path(out).write_text(
            json.dumps(report, indent=2, sort_keys=True) + "\n",
            encoding="utf-8",
        )
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--fault_profile", type=str, default=DEFAULT_FAULT_PROFILE,
        help="fault grammar (resilience/faults.py); mesh=kill:<device>"
             "@<superstep> events mark mesh devices lost at superstep "
             "boundaries",
    )
    ap.add_argument("--quick", action="store_true",
                    help=f"CI shape: {QUICK_CONFIG}")
    ap.add_argument("--workdir", type=str, default=None,
                    help="scratch dir (default: a fresh temp dir)")
    ap.add_argument("--out", type=str, default="elastic_report.json",
                    help="report path (always printed to stdout)")
    args = ap.parse_args(argv)

    # the virtual mesh must exist before jax initializes — same
    # mechanism as the sharded-runtime tests
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{VIRTUAL_DEVICES}"
        ).strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from gymfx_tpu.config.defaults import DEFAULT_VALUES

    config = dict(DEFAULT_VALUES)
    config.update(QUICK_CONFIG)  # the CI shape is the only shape for now
    if not args.quick:
        config["train_total_steps"] = 16 * 8 * 6  # 6 iterations

    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        workdir = args.workdir or tmp
        report = run_elastic_chaos(
            config,
            fault_profile=args.fault_profile,
            workdir=workdir,
            out=args.out,
        )
    problems = validate_elastic_report(report)
    if problems:  # emitter bug — fail loudly, never ship a bad report
        for p in problems:
            print(f"ELASTIC REPORT SCHEMA VIOLATION: {p}", file=sys.stderr)
        return 2
    print(json.dumps(report, indent=2, sort_keys=True))
    if not report["passed"]:
        print(
            f"elastic chaos FAILED: attempts={report['attempts']} "
            f"degrades={report['degrades']} resumes={report['resumes']} "
            f"lost_supersteps={report['lost_supersteps_past_checkpoint']} "
            f"replay_parity={report['replay_parity']} "
            f"ledger_valid={report['ledger_valid']}",
            file=sys.stderr,
        )
        return 1
    print(
        f"elastic chaos OK (mesh {report['mesh_before']} -> "
        f"{report['mesh_after']}, {report['degrades']} degrade(s), "
        f"{report['resumes']} verified resume(s), "
        f"{report['lost_supersteps_past_checkpoint']} supersteps lost "
        f"past the last checkpoint, replay bitwise-identical)",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
