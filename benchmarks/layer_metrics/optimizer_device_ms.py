"""Device self time per train step of the fused step (scope_times.py),
``update/optimizer``: optimizer update, ``apply_updates`` and
the non-finite guard's select."""
from scope_times import ms


def read(run):
    return ms(run, "update/optimizer")
