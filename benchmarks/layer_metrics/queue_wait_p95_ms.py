"""95th percentile of the batcher's own ``RequestRecord.queue_wait_s``
(enqueue to dispatch) over the window's requests."""
import numpy as np


def read(run):
    records = run.get("records")
    if not records:
        return None
    return 1e3 * float(np.percentile([r.queue_wait_s for r in records], 95))
