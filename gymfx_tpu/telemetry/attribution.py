"""Measured-MFU attribution: trace -> schema-pinned profile report.

The closing arc of the performance observatory: take one capture bundle
(:mod:`gymfx_tpu.telemetry.profiler`), parse its device timeline
(:mod:`gymfx_tpu.telemetry.trace_parse`), and reconcile what the
hardware *measured* against what the repo previously only *inferred* —
the ``bench_util.measure_phase_split`` wall split and the analytic FLOP
model (:mod:`gymfx_tpu.telemetry.mfu`).  The output is one
``profile_report.json``:

  * ``trace``          device/host lanes, busy vs window time, the
                       dispatch gap (host overhead), fusion coverage,
                       and the top-N kernel table
  * ``phases``         device time grouped under the rollout/update
                       ``jax.named_scope`` annotations
  * ``reconciliation`` trace-attributed phase fractions vs the
                       phase-split baseline the capture manifest
                       carries, with a tolerance verdict
  * ``mfu_measured``   FLOPs over *measured device time* — the
                       measured twin of the ``mfu_analytic`` block
                       (``mfu`` itself stays null where the chip's
                       peak is unknown, the repo-wide CPU convention)

pinned by the committed ``profile_report_schema.json`` next to this
module; :func:`validate_profile_report` is the one validator tests,
``tools/profile_report.py`` and the run_tests.sh smoke share.
:func:`compare_profile_reports` diffs two reports at a per-kernel
regression threshold — the hook ``tools/bench_sentinel.py`` uses to
gate kernel-level regressions, not just end-to-end steps/sec.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

from gymfx_tpu.telemetry.profiler import MANIFEST_NAME, SCOPE_MAP_NAME
from gymfx_tpu.telemetry.scopes import PHASE_SCOPES
from gymfx_tpu.telemetry.trace_parse import group_by_scope, parse_trace

SCHEMA_PATH = Path(__file__).resolve().parent / "profile_report_schema.json"

PROFILE_REPORT_SCHEMA_VERSION = 1

# phase-attribution agreement the CI smoke demands: the trace-measured
# rollout fraction within this of the measure_phase_split fraction
DEFAULT_TOLERANCE = 0.25

_MANIFEST_ECHO_KEYS = (
    "config_sha256", "it_start", "k", "it_end", "label",
    "platform", "device_kind", "comparable", "hw_flops_peak",
    "algo", "n_envs", "horizon", "steps_per_iter", "fingerprints",
)


def _round(value: Optional[float], digits: int = 4) -> Optional[float]:
    return None if value is None else round(float(value), digits)


def _load_json(path: Path) -> Dict[str, Any]:
    try:
        doc = json.loads(path.read_text(encoding="utf-8"))
        return doc if isinstance(doc, dict) else {}
    except Exception:
        return {}


def build_profile_report(
    capture_dir: str,
    *,
    top_n: int = 15,
    tolerance: float = DEFAULT_TOLERANCE,
    scopes: Sequence[str] = PHASE_SCOPES,
) -> Dict[str, Any]:
    """One capture bundle -> the report dict (never raises; a broken
    bundle yields ``trace.ok=False`` and null attribution)."""
    bundle = Path(capture_dir)
    manifest = _load_json(bundle / MANIFEST_NAME)
    scope_map = _load_json(bundle / str(
        manifest.get("scope_map_file") or SCOPE_MAP_NAME
    ))
    summary = parse_trace(str(bundle), scopes=scopes)
    groups = group_by_scope(summary, scope_map, scopes=scopes)

    k = manifest.get("k")
    k = int(k) if isinstance(k, (int, float)) and k else 1
    busy_ms = summary["device_busy_us"] / 1e3
    window_ms = summary["window_us"] / 1e3
    gap_ms = max(0.0, window_ms - busy_ms)
    total_op_ms = summary["device_total_us"] / 1e3

    ops = summary.get("ops") or {}
    fusion_ms = sum(
        op["total_us"] for name, op in ops.items() if "fusion" in name
    ) / 1e3
    top = sorted(
        ops.items(), key=lambda kv: kv[1]["total_us"], reverse=True
    )[: max(0, int(top_n))]
    top_kernels = []
    for name, op in top:
        scope = op.get("scope")
        if scope not in scopes:
            mapped = scope_map.get(name)
            scope = mapped if mapped in scopes else None
        ms = op["total_us"] / 1e3
        top_kernels.append({
            "name": name,
            "count": int(op["count"]),
            "total_ms": _round(ms),
            "total_ms_per_step": _round(ms / k),
            "frac": _round(ms / total_op_ms if total_op_ms else 0.0),
            "scope": scope,
        })

    # -- phases: device op time under the named_scope annotations ------
    phase_ms = {scope: groups.get(scope, 0.0) / 1e3 for scope in scopes}
    unattributed_ms = groups.get("unattributed", 0.0) / 1e3
    attributed_ms = sum(phase_ms.values())
    rollout_ms = phase_ms.get("rollout", 0.0)
    update_ms = phase_ms.get("update", 0.0)
    rollout_frac = update_frac = None
    if attributed_ms > 0:
        rollout_frac = rollout_ms / attributed_ms
        update_frac = update_ms / attributed_ms
    phases = {
        "rollout_ms": _round(rollout_ms),
        "update_ms": _round(update_ms),
        "unattributed_ms": _round(unattributed_ms),
        "rollout_frac": _round(rollout_frac),
        "update_frac": _round(update_frac),
        # how much of the device op time the scope map explained at all
        "attributed_frac": _round(
            attributed_ms / total_op_ms if total_op_ms else 0.0
        ),
    }

    # -- reconciliation vs the measure_phase_split baseline ------------
    split = manifest.get("phase_split") or {}
    split_rollout = split.get("rollout_ms")
    split_update = split.get("update_ms")
    split_rollout_frac = None
    if (isinstance(split_rollout, (int, float))
            and isinstance(split_update, (int, float))
            and (split_rollout + split_update) > 0):
        split_rollout_frac = split_rollout / (split_rollout + split_update)
    err = within = None
    if split_rollout_frac is not None and rollout_frac is not None:
        err = abs(rollout_frac - split_rollout_frac)
        # relative to the split fraction, floored at an absolute share
        # so a tiny phase cannot explode the ratio
        within = bool(
            err <= float(tolerance) * max(split_rollout_frac, 0.05)
            or err <= float(tolerance) * 0.5
        )
    reconciliation = {
        "split_rollout_ms": _round(split_rollout),
        "split_update_ms": _round(split_update),
        "split_rollout_frac": _round(split_rollout_frac),
        "trace_rollout_frac": _round(rollout_frac),
        "rollout_frac_abs_err": _round(err),
        "tolerance": float(tolerance),
        "within_tolerance": within,
        "split_source": split.get("source"),
    }

    # -- measured MFU: FLOPs over measured device time -----------------
    device_ms_per_step = (busy_ms / k) if busy_ms > 0 else None
    xla_flops = manifest.get("xla_flops_per_step")
    analytic_flops = manifest.get("analytic_flops_per_step")
    flops, flops_source = None, None
    if isinstance(xla_flops, (int, float)) and xla_flops > 0:
        flops, flops_source = float(xla_flops), "xla"
    elif isinstance(analytic_flops, (int, float)) and analytic_flops > 0:
        flops, flops_source = float(analytic_flops), "analytic"
    achieved = None
    if flops is not None and device_ms_per_step:
        achieved = flops / (device_ms_per_step / 1e3)
    peak = manifest.get("hw_flops_peak")
    peak = float(peak) if isinstance(peak, (int, float)) and peak > 0 else None
    mfu_measured = {
        "device_ms_per_step": _round(device_ms_per_step),
        "flops_per_step": flops,
        "flops_source": flops_source,
        "achieved_flops_per_sec": _round(achieved, 1),
        "hw_flops_peak": peak,
        # null where the chip's public peak is unknown (CPU) — same
        # convention as mfu_analytic on every bench row
        "mfu": _round(
            achieved / peak if achieved is not None and peak else None, 5
        ),
    }
    analytic_mfu = None
    if (isinstance(analytic_flops, (int, float)) and analytic_flops > 0
            and peak and device_ms_per_step):
        analytic_mfu = analytic_flops / (device_ms_per_step / 1e3) / peak
    mfu_analytic = {
        "analytic_flops_per_step": (
            float(analytic_flops)
            if isinstance(analytic_flops, (int, float)) else None
        ),
        "hw_flops_peak": peak,
        "mfu_analytic": _round(analytic_mfu, 5),
    }

    return {
        "schema_version": PROFILE_REPORT_SCHEMA_VERSION,
        "capture_dir": str(bundle),
        "manifest": {
            key: manifest.get(key) for key in _MANIFEST_ECHO_KEYS
        },
        "trace": {
            "ok": bool(summary.get("ok")),
            "error": summary.get("error"),
            "events": int(summary.get("events", 0)),
            "device_lanes": summary.get("device_lanes", []),
            "host_lanes": summary.get("host_lanes", []),
            "device_busy_ms": _round(busy_ms),
            "device_op_ms": _round(total_op_ms),
            "window_ms": _round(window_ms),
            "dispatch_gap_ms": _round(gap_ms),
            "dispatch_gap_frac": _round(
                gap_ms / window_ms if window_ms else None
            ),
            "fusion_coverage": _round(
                fusion_ms / total_op_ms if total_op_ms else None
            ),
            "top_kernels": top_kernels,
        },
        "phases": phases,
        "reconciliation": reconciliation,
        "mfu_measured": mfu_measured,
        "mfu_analytic": mfu_analytic,
    }


# ---------------------------------------------------------------------------
# validation: the committed schema, shared by tier-1 and the CI smoke
def load_profile_report_schema() -> Dict[str, Any]:
    with open(SCHEMA_PATH, encoding="utf-8") as fh:
        schema = json.load(fh)
    schema.pop("_comment", None)
    return schema


def validate_profile_report(
    report: Dict[str, Any],
    schema: Optional[Dict[str, Any]] = None,
) -> List[str]:
    """Return a list of violations (empty = the report conforms):
    top-level sections, per-section required keys, and per-kernel row
    keys — presence-pinned like the bench contract (values may be null
    where the backend cannot say)."""
    if schema is None:
        schema = load_profile_report_schema()
    problems: List[str] = []
    if not isinstance(report, dict):
        return ["report is not a JSON object"]
    for key in schema.get("required", ()):
        if key not in report:
            problems.append(f"missing top-level key {key!r}")
    version = report.get("schema_version")
    if version != schema.get("schema_version"):
        problems.append(
            f"schema_version {version!r} != {schema.get('schema_version')!r}"
        )
    for section, req_key in (
        ("manifest", "manifest_required"),
        ("trace", "trace_required"),
        ("phases", "phases_required"),
        ("reconciliation", "reconciliation_required"),
        ("mfu_measured", "mfu_measured_required"),
        ("mfu_analytic", "mfu_analytic_required"),
    ):
        block = report.get(section)
        if not isinstance(block, dict):
            problems.append(f"section {section!r} is not an object")
            continue
        for key in schema.get(req_key, ()):
            if key not in block:
                problems.append(f"{section}: missing required key {key!r}")
    kernels = (report.get("trace") or {}).get("top_kernels")
    if isinstance(kernels, list):
        for i, row in enumerate(kernels):
            if not isinstance(row, dict):
                problems.append(f"top_kernels[{i}]: not an object")
                continue
            for key in schema.get("kernel_required", ()):
                if key not in row:
                    problems.append(
                        f"top_kernels[{i}]: missing required key {key!r}"
                    )
    else:
        problems.append("trace.top_kernels is not a list")
    return problems


# ---------------------------------------------------------------------------
def compare_profile_reports(
    base: Dict[str, Any],
    new: Dict[str, Any],
    *,
    threshold: float = DEFAULT_TOLERANCE,
    min_ms: float = 0.05,
) -> Dict[str, Any]:
    """Per-kernel regression diff of two reports: a kernel regresses
    when its per-step time grows more than ``threshold`` over the base
    (kernels under ``min_ms`` per step are noise and skipped), and the
    end-to-end device time is gated the same way.  ``ok`` is the gate
    verdict; ``comparable`` records whether the two captures came from
    the same platform/device_kind (the caller decides whether a
    non-comparable pair should gate)."""
    def _kernels(report: Dict[str, Any]) -> Dict[str, float]:
        out = {}
        for row in (report.get("trace") or {}).get("top_kernels") or []:
            ms = row.get("total_ms_per_step")
            if isinstance(row.get("name"), str) and isinstance(
                    ms, (int, float)):
                out[row["name"]] = float(ms)
        return out

    base_m = base.get("manifest") or {}
    new_m = new.get("manifest") or {}
    comparable = (
        base_m.get("platform") == new_m.get("platform")
        and base_m.get("device_kind") == new_m.get("device_kind")
    )
    base_k, new_k = _kernels(base), _kernels(new)
    regressions: List[Dict[str, Any]] = []
    improvements: List[Dict[str, Any]] = []
    for name in sorted(set(base_k) & set(new_k)):
        b, n = base_k[name], new_k[name]
        if b < float(min_ms):
            continue
        ratio = n / b if b > 0 else None
        entry = {
            "kind": "kernel", "name": name,
            "base_ms_per_step": round(b, 4), "new_ms_per_step": round(n, 4),
            "ratio": round(ratio, 4) if ratio is not None else None,
        }
        if ratio is not None and ratio > 1.0 + float(threshold):
            regressions.append(entry)
        elif ratio is not None and ratio < 1.0 - float(threshold):
            improvements.append(entry)
    b_step = (base.get("mfu_measured") or {}).get("device_ms_per_step")
    n_step = (new.get("mfu_measured") or {}).get("device_ms_per_step")
    if (isinstance(b_step, (int, float)) and isinstance(n_step, (int, float))
            and b_step > 0):
        ratio = n_step / b_step
        entry = {
            "kind": "device_time",
            "name": "device_ms_per_step",
            "base_ms_per_step": round(float(b_step), 4),
            "new_ms_per_step": round(float(n_step), 4),
            "ratio": round(ratio, 4),
        }
        if ratio > 1.0 + float(threshold):
            regressions.append(entry)
        elif ratio < 1.0 - float(threshold):
            improvements.append(entry)
    return {
        "threshold": float(threshold),
        "min_ms": float(min_ms),
        "comparable": bool(comparable),
        "only_in_base": sorted(set(base_k) - set(new_k)),
        "only_in_new": sorted(set(new_k) - set(base_k)),
        "regressions": regressions,
        "improvements": improvements,
        "ok": not regressions,
    }
