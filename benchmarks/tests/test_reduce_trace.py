"""The trace reduction: its interval arithmetic on hand-made events, and the
whole reduction against one small trace recorded on the chip
(``data/small.xplane.pb``, made by record_trace.py on "TPU v5 lite").
Run by hand: ``python -m pytest benchmarks/tests -q`` (not part of tier-1)."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent)]

import reduce_trace  # noqa: E402

TRACE = HERE / "data" / "small.xplane.pb"


def test_union_merges_overlaps_and_keeps_gaps():
    assert reduce_trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_an_enclosing_while_is_charged_only_what_its_body_leaves():
    events = [
        ("while.1", 0, 100, False),
        ("fusion.1", 10, 30, False),
        ("kernel.1 (tpu_custom_call)", 30, 70, True),
        ("fusion.1", 80, 90, False),
        ("copy.1", 120, 130, False),
    ]
    by_name, kernel_ns = reduce_trace.self_times(events)
    assert by_name == {"while.1": 30, "fusion.1": 30,
                       "kernel.1 (tpu_custom_call)": 40, "copy.1": 10}
    assert kernel_ns == 40
    assert sum(by_name.values()) == sum(e - s for s, e in reduce_trace.union(
        (s, e) for _n, s, e, _k in events))


def test_short_names_keep_the_kernel_mark():
    text = ('%attn.7 = bf16[8,4]{1,0} custom-call(bf16[8,4]{1,0} %p), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert reduce_trace.short(text) == "attn.7 (tpu_custom_call)"
    assert reduce_trace.short("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %x)") == "fusion.12"
    assert reduce_trace.short('%custom-call.5 = f32[4]{0} custom-call(), '
                              'custom_call_target="AllocateBuffer"') == "custom-call.5"


def test_idle_share():
    assert reduce_trace.idle_share({"busy_s": 0.25, "window_s": 1.0}) == 75.0
    assert reduce_trace.idle_share({}) is None


@pytest.mark.skipif(not TRACE.exists(), reason="no recorded chip trace")
def test_reduction_of_the_recorded_chip_trace():
    got = reduce_trace.reduce(TRACE)
    expected = json.loads((HERE / "data" / "small.expected.json").read_text())
    for key in ("busy_s", "window_s", "kernel_s", "device_planes"):
        assert got[key] == pytest.approx(expected[key], rel=1e-9), key
    assert got["device_ops"] == expected["device_ops"]
    assert got["idle_gaps"] == expected["idle_gaps"]
    # what must hold of any chip trace of this program (see record_trace.py)
    assert got["device_planes"] == 1 and 0 < got["busy_s"] < got["window_s"]
    ops = dict(map(tuple, got["device_ops"]))
    assert sum(ops.values()) == pytest.approx(got["busy_s"], rel=1e-6)
    kernels = {n: s for n, s in ops.items() if n.endswith("(tpu_custom_call)")}
    assert kernels and sum(kernels.values()) == pytest.approx(got["kernel_s"], rel=1e-9)
    assert any(n.startswith("while") for n in ops)
    gaps = dict(map(tuple, got["idle_gaps"]))
    assert sum(gaps.values()) == pytest.approx(got["window_s"] - got["busy_s"], rel=1e-6)
    # the 2 ms host sleeps sit under the fetch span: 3 of them
    assert gaps["bench.fetch_loss"] > 0.005
