"""Per cent of the device's busy time in the traced window that
scope_times.py charges to a layer of the step: under ``rollout`` or
``update`` and not to a scope that only groups layers (``rollout``,
``update``, ``rollout/env_step`` themselves).  What is left is the scans'
bookkeeping, ops XLA added or left between two layers, and ops with no
scope; the ``scope_ms`` note line lists them."""
from scope_times import in_a_layer, table_of


def read(run):
    table = table_of(run)
    if table is None:
        return None
    return 100.0 * in_a_layer(table) / table["busy_s"]
