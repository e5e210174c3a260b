"""95th percentile of (send time - due time) of the benchmark's own load
generator: a starved generator must not read as a fast server."""


def read(run):
    late = run["spans"].get("generator_late_ms")
    return late[95] if late else None
