"""Device self time per train step of the fused step (scope_times.py),
every ``.../ffn`` scope, rollout and update, forward and
backward: layer norm and the two ``Dense`` of each transformer block."""
from scope_times import ms


def read(run):
    return ms(run, last="ffn")
