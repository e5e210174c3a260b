"""Device self time per train step of the fused step (scope_times.py),
everything under ``rollout``: env step, policy forward,
auto-reset and the scan's own bookkeeping."""
from scope_times import ms


def read(run):
    return ms(run, "rollout")
