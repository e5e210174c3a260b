#!/usr/bin/env python3
"""Scan-engine determinism evidence (SURVEY.md §4 pattern 3): run the
same seeded episode repeatedly in-process AND across spawned processes,
hash the full output stream, and assert all hashes agree.  Emits
schema-versioned evidence JSON."""
import hashlib
import json
import multiprocessing as mp
import os
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# Determinism evidence has no reason to touch an accelerator, and a chip
# belongs to ONE process: this launcher runs episodes itself and then in
# a spawn pool, so parent and workers (which re-import this module) are
# all pinned to the CPU before any of them touches JAX.
os.environ["JAX_PLATFORMS"] = "cpu"


def episode_hash(_=None):
    import numpy as np

    from gymfx_tpu.config import DEFAULT_VALUES
    from gymfx_tpu.core import rollout as R
    from gymfx_tpu.core.runtime import Environment

    config = dict(DEFAULT_VALUES)
    config.update(
        input_data_file=str(REPO / "examples" / "data" / "eurusd_sample.csv"),
        strategy_plugin="direct_atr_sltp",
        commission=2e-5,
        slippage=1e-5,
    )
    env = Environment(config)
    state, out = env.rollout(R.random_driver(), steps=300, seed=42)
    h = hashlib.sha256()
    for key in sorted(out):
        h.update(key.encode())
        h.update(np.ascontiguousarray(np.asarray(out[key])).tobytes())
    h.update(np.asarray(state.equity_delta).tobytes())
    return "sha256:" + h.hexdigest()


def main() -> int:
    in_process = [episode_hash() for _ in range(3)]
    ctx = mp.get_context("spawn")
    with ctx.Pool(2) as pool:
        cross_process = pool.map(episode_hash, range(2))
    all_hashes = set(in_process) | set(cross_process)
    evidence = {
        "schema": "scan_engine_determinism.v1",
        "runs_in_process": len(in_process),
        "runs_cross_process": len(cross_process),
        "hash": in_process[0],
        "deterministic": len(all_hashes) == 1,
    }
    if len(all_hashes) > 1:  # make divergence diagnosable from the artifact
        evidence["hashes_in_process"] = in_process
        evidence["hashes_cross_process"] = cross_process
    out = REPO / "examples" / "results" / "scan_determinism.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(evidence, indent=2))
    print(json.dumps(evidence, indent=2))
    return 0 if evidence["deterministic"] else 1


if __name__ == "__main__":
    sys.exit(main())
