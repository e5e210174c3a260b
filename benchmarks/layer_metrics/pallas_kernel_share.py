"""Device time of the Mosaic (Pallas) custom calls over device busy time,
both from the traced window's reduction."""


def read(run):
    trace = run.get("trace") or {}
    if not trace.get("busy_s"):
        return None
    return 100.0 * trace["kernel_s"] / trace["busy_s"]
