"""Per cent of the chip's bf16 peak that the REQUIRED operations of the
decisions answered in the traced window are, over the device's busy time
there: ``decisions_traced`` x window x ``rooflines/mla_moe_decoder.py::
forward_flops_per_token`` (the share held, active experts only, at the expected
routing; causal attention; counted from the configuration file alone: a padded
row of a bucket is work the program chose, not work a decision requires) ÷
(``busy_s`` x ``peaks.json``'s bf16 peak).  The share of the whole serving step
that later claims on a serving cell are bounded by.  Nothing on a device that
is not in ``peaks.json`` (a rehearsal's CPU)."""
import harness


def read(run):
    rooflines = harness.load_module("rooflines", "mla_moe_decoder")
    trace = run.get("trace") or {}
    decisions = run.get("counters", {}).get("decisions_traced")
    peak = rooflines.device_peak()
    if not trace.get("busy_s") or not decisions or peak is None:
        return None
    program = run["cell"]["config"]["program"]
    sizes = {**program["policy_kwargs"], "window": int(program["window_size"])}
    flops = decisions * sizes["window"] * sum(
        rooflines.forward_flops_per_token(sizes).values())
    return 100.0 * flops / (trace["busy_s"] * peak["bf16_flops_per_s"])
