"""Device self time per train step of the fused step (scope_times.py),
``rollout/env_step/tape_read``: the tape's columns read by
the envs' bar index (``core/env.py``)."""
from scope_times import ms


def read(run):
    return ms(run, "rollout/env_step/tape_read")
