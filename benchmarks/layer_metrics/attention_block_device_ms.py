"""Device self time per train step of the fused step (scope_times.py),
every ``.../attention`` scope, rollout and update, forward
and backward: layer norm, q/k/v projections, the attention call (the fused
kernels included) and the output projection of each transformer block."""
from scope_times import ms


def read(run):
    return ms(run, last="attention")
