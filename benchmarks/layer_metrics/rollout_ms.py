"""Median time of the ``_rollout_phase`` program (env step + policy
forward over one horizon), synchronised, host clock."""
import statistics


def read(run):
    times = run["spans"].get("rollout_s")
    return 1e3 * statistics.median(times) if times else None
