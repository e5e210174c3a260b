"""Useful rows over padded rows dispatched: every record of a dispatch of
``batch_size`` requests carries its ``bucket``, so the sum of
``bucket / batch_size`` over the window's records is the padded rows."""


def read(run):
    records = run.get("records")
    if not records:
        return None
    return 100.0 * len(records) / sum(r.bucket / r.batch_size for r in records)
