"""Device self time per train step of the fused step (scope_times.py), every
``.../short_conv`` scope, rollout and update, forward and backward: the gated
short convolution half of each block whose kind is ``conv`` -- pre-norm, the one
projection to B | C | u, the gates, the causal taps, the output product
(``gymfx_tpu/train/mla_moe_decoder.py::ShortConv``; docs/observability.md).  A
program without the scope: nothing."""
from scope_times import ms


def read(run):
    return ms(run, last="short_conv") or None
