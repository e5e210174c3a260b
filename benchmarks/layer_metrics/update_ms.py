"""Median time of the ``_update_phase`` program (GAE + minibatched epochs),
synchronised, host clock."""
import statistics


def read(run):
    times = run["spans"].get("update_s")
    return 1e3 * statistics.median(times) if times else None
