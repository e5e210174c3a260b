"""Per cent of the chip's bf16 peak that the REQUIRED operations of one train
step of the convolution-attention decoder trunk are, over the device's busy time
a step in the traced window: ``rooflines/conv_hybrid_decoder.py::train_step_flops``
(the share of the model held, active experts only, causal scores, no
recomputation) ÷ (``busy_s`` ÷ train steps × ``peaks.json``'s bf16 peak).  Nothing
on a device that is not in ``peaks.json`` (a rehearsal's CPU), and nothing for a
program whose policy has no ``layer_types``."""
import harness


def read(run):
    rooflines = harness.load_module("rooflines", "conv_hybrid_decoder")
    trace, steps = run.get("trace") or {}, run.get("counters", {}).get("train_steps")
    peak = rooflines.device_peak()
    if not trace.get("busy_s") or not steps or peak is None:
        return None
    flops = rooflines.train_step_flops(run["cell"])
    return 100.0 * flops / (trace["busy_s"] / steps * peak["bf16_flops_per_s"])
