"""Device self time per train step of the fused step that the PART map
(part_times.py) leaves with no path at all: what the ``(no scope)`` row of
the ``scope_ms`` note holds less the copies and async pairs the part map names
after their users.  The ``part_ms`` note line lists its ten largest ops with
their opcodes.  A program without the part map: nothing."""
from part_times import table_of, unnamed


def read(run):
    table = table_of(run)
    if table is None:
        return None
    return 1e3 * unnamed(table)
