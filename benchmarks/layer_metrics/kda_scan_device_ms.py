"""Device self time per train step of the fused step (part_times.py), every
part path ending in ``kda_scan``, rollout and update, forward and backward:
the whole gated-delta-rule scan of each Kimi-Delta-Attention layer -- the
loop over windows, the chunks' parts, the unit-lower inverse, the walk, and
the scan's own backward pass (``gymfx_tpu/ops/kda_chunk_scan.py``), without
the projections, taps, gated norm and output product that
``linear_attention_block_device_ms`` also holds.  A program without the part
map: nothing."""
from part_times import ms


def read(run):
    return ms(run, last="kda_scan") or None
