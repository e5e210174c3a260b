"""Device self time per train step of the fused step (scope_times.py),
``update/loss`` in the backward direction (op path under
``transpose(...)``).  A forward recomputed inside the backward pass
(``ppo_update_remat``) is charged here, not to ``loss_forward_device_ms``."""
from scope_times import ms


def read(run):
    return ms(run, "update/loss", direction="bwd")
