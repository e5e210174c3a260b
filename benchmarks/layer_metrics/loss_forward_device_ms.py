"""Device self time per train step of the fused step (scope_times.py),
``update/loss`` in the forward direction (op path under
``jvp(...)`` and not ``transpose(...)``): the policy forward and the loss."""
from scope_times import ms


def read(run):
    return ms(run, "update/loss", direction="fwd")
