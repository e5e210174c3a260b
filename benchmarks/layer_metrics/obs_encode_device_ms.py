"""Device self time per train step of the fused step (scope_times.py),
``rollout/env_step/obs``: the obs window update,
``build_obs`` and the trainer's encode."""
from scope_times import ms


def read(run):
    return ms(run, "rollout/env_step/obs")
