"""Device self time per train step of the fused step (scope_times.py), every
``.../linear_attention`` scope, rollout and update, forward and backward: the
Kimi-Delta-Attention half of each hybrid block -- pre-norm, the q/k/v, decay,
beta and gate projections, the short convolutions, the chunked scan, the gated
head norm and the output product (``gymfx_tpu/train/mla_moe_decoder.py``;
docs/observability.md).  A program without the scope: nothing."""
from scope_times import ms


def read(run):
    return ms(run, last="linear_attention") or None
