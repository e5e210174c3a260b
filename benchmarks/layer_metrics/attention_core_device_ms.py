"""Device self time per train step of the fused step (part_times.py), every
part path ending in ``attention_core``, rollout and update, forward and
backward: what lies between the q / k / v products' outputs and the output
product's input -- head norms, RoPE, the k | v split, the layout (transposes,
the value pad, the repeat to the query heads, lane packing) and the attention
call, fused kernels or plain twin (``train/mla_moe_decoder.py``,
``train/policies.py``).  The pre-norm, the products and the output gate stay
in ``attention_block_device_ms`` alone.  A program without the part map:
nothing."""
from part_times import ms


def read(run):
    return ms(run, last="attention_core") or None
