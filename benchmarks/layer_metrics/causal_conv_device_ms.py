"""Device self time per train step of the fused step (part_times.py), every
part path ending in ``causal_conv``, rollout and update, forward and backward:
the pad and the shifted multiply-adds of each short causal convolution
(``gymfx_tpu/ops/kda_chunk_scan.py::causal_conv``: KDA's four taps on q, k
and v, ``ShortConv``'s three), without the gates, SiLU and projections around
them.  A program without the part map: nothing."""
from part_times import ms


def read(run):
    return ms(run, last="causal_conv") or None
