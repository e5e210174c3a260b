"""Device self time per train step of the fused step (scope_times.py),
everything under ``update``: GAE, minibatching, loss forward
and backward, optimizer, guard."""
from scope_times import ms


def read(run):
    return ms(run, "update")
