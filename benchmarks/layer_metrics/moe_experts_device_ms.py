"""Device self time per train step of the fused step (scope_times.py),
every ``.../moe_experts`` scope of an expert layer, rollout and update, forward
and backward (``gymfx_tpu/train/mla_moe_decoder.py``; docs/observability.md
says what each of the four holds).  A program without the scope: nothing."""
from scope_times import ms


def read(run):
    return ms(run, last="moe_experts") or None
