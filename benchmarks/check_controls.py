#!/usr/bin/env python3
"""One run of a cell with its check's controls read, or with a fault planted in
the program (the tool that sets a check's limits, not a cell's run): the
arguments of ``run.py``, and optionally ``--fault <name>``.

    python benchmarks/check_controls.py --workload <cell> --seed <n> --seconds <s> --trace 0
    python benchmarks/check_controls.py --fault half_batch --workload <cell> ...

Without ``--fault`` the cell's ``check`` gets ``run_controls``: after the
program's answers, the check also takes what each control reads
(``checks/<kind>.py``: the reference put in the program's place under a lower
precision or a fault) and puts every one through the same limits; the note line
gives each control's readings and the limits that refuse it.  With ``--fault``
the run is the cell's own, with the timed path broken underneath (``FAULTS``);
its ``correct`` has to come out false.  One process, as a run of the cell is.
"""
from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent)]

import run  # noqa: E402


def half_batch():
    """The serving engine answers the back half of every batch of two or more
    (rows from ceil(n / 2) on) with the mean of the front half's answers: half
    of the batch left out, the mean taken over the rest."""
    import numpy as np

    from gymfx_tpu.serve import engine

    decide = engine.InferenceEngine.decide_batch

    def left_out(self, obs_batch, carries=None):
        out = decide(self, obs_batch, carries)
        n = len(out.value)
        if n < 2:
            return out
        front = -(-n // 2)
        actor, value = np.array(out.actor_out), np.array(out.value)
        actor[front:] = actor[:front].mean(axis=0)
        value[front:] = value[:front].mean(axis=0)
        action = np.array(out.action)
        action[front:] = actor[front:].argmax(-1)
        return out._replace(actor_out=actor, value=value, action=action)

    engine.InferenceEngine.decide_batch = left_out


FAULTS = {"half_batch": half_batch}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    fault = None
    if "--fault" in argv:
        at = argv.index("--fault")
        fault = argv[at + 1]
        del argv[at:at + 2]
    if fault is not None:
        FAULTS[fault]()
        return run.main(argv)
    load_cell = run.load_cell

    def with_controls(*args, **kwargs):
        cell = load_cell(*args, **kwargs)
        cell["config"]["check"]["run_controls"] = True
        return cell

    run.load_cell = with_controls
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
