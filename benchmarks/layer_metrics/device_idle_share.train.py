"""1 - (union of device-op intervals / traced window), trainer cells."""
from reduce_trace import idle_share


def read(run):
    return idle_share(run.get("trace"))
