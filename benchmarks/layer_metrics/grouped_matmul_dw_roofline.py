"""Roofline share of the Mosaic kernel ``grouped_matmul_dw`` (``gymfx_tpu/ops/
grouped_matmul.py``) in the traced window: the least time its calls of one
train step could take (``rooflines/mla_moe_decoder.py``: per call the
larger of operations over the bf16 peak and bytes over the HBM peak, rows as
the traced steps really routed them: ``traced_held_share``, the program's
counter ``moe_held_share`` as the cell's check left it) ÷ the device time of the calls named ``grouped_matmul_dw.<n>``.
Nothing where the step holds no such call or the device has no peaks."""
import harness


def read(run):
    rooflines = harness.load_module("rooflines", "mla_moe_decoder")
    return rooflines.kernel_roofline_share(run, "grouped_matmul_dw")
