"""The trace reduction: names, busy time, kernel time and idle gaps, on a
trace recorded on the chip (a slice of ``rwkv6-7b-q3.decode``) and on
hand-made intervals."""
import os

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_overlaps_and_nesting():
    assert trace._union([(5, 9), (0, 2), (1, 3), (6, 7)]) == [(0, 3), (5, 9)]


def test_short_names():
    loop = ("%while.10 = (s32[]{:T(128)}, bf16[32,4096]{1,0}) "
            "while((s32[]{:T(128)}, bf16[32,4096]{1,0}) %tuple), body=%b")
    call = ("%fn.1 = bf16[32,4096]{1,0:T(8,128)(2,1)} custom-call(%iota, "
            "%x), custom_call_target=\"tpu_custom_call\", metadata={}")
    fusion = "%fusion.12 = bf16[32,4096]{1,0} fusion(%a, %b), kind=kLoop"
    assert trace.short_name(loop) == "while.10 (while)"
    assert trace.short_name(call) == "fn.1 (tpu_custom_call)"
    assert trace.short_name(fusion) == "fusion.12 (fusion)"
    assert trace.is_kernel(call) and not trace.is_kernel(fusion)


class _Slice:
    traced = True
    window_s = 0.5


def test_recorded_decode_slice():
    """Three engine steps of ``rwkv6-7b-q3.decode`` at capacity 32 on a
    TPU v5 lite (``data/decode_slice.xplane.pb``)."""
    red = trace.reduce(DATA, _Slice())
    assert red["events"] > 1000
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0 < red["kernel_s"] < red["busy_s"]
    assert 0 < len(red["device_ops"]) <= 10
    assert 0 < len(red["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in red["device_ops"] + red["idle_gaps"])
    names = [n for n, _ in red["device_ops"]]
    assert not any(n.endswith("(while)") for n in names)
    assert any(n.endswith("(tpu_custom_call)") for n in names)
    assert {n for n, _ in red["idle_gaps"]} <= {"bench.engine_step", "host"}
