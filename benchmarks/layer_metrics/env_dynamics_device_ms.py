"""Device self time per train step of the fused step (scope_times.py),
``rollout/env_step/dynamics``: action coercion, fills and
brackets, financing, margin, mark, reward, termination; the env-dynamics
kernels A and B included."""
from scope_times import ms


def read(run):
    return ms(run, "rollout/env_step/dynamics")
