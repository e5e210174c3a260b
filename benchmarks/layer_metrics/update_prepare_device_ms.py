"""Device self time per train step of the fused step (scope_times.py),
``update/gae`` + ``update/minibatch_take``: advantages and
returns, then permutation, slice and gather of each minibatch."""
from scope_times import ms


def read(run):
    return ms(run, "update/gae", "update/minibatch_take")
