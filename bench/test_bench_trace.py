"""The trace reduction (bench/xtrace.py) on a small synthetic trace."""
import pytest

from bench import xtrace
from bench.xtrace import Interval as I


def raw():
    # window 0..10 s; ops overlap in 0..2; one kernel event of 0.5 s
    return xtrace.RawTrace(
        ops={"/device:TPU:0": [I("fusion", 0.0, 1.0), I("copy", 0.5, 2.0),
                               I("paged_decode_attention", 5.0, 5.5),
                               I("fusion", 5.5, 6.0),
                               I("outside", 11.0, 12.0)]},
        modules={"/device:TPU:0": [I("jit_tick", 0.0, 2.0),
                                   I("jit_tick", 5.0, 6.0),
                                   I("jit_prefill", 11.0, 12.0)]},
        host=[I("bench.window", 0.0, 10.0), I("bench.step", 1.5, 5.5),
              I("bench.stamp", 2.5, 2.6), I("bench.wait", 6.5, 10.0)])


def test_busy_is_the_union_of_op_intervals():
    s = xtrace.reduce(raw())
    assert s.window_s == pytest.approx(10.0)
    assert s.busy_s == pytest.approx(3.0)          # [0, 2] and [5, 6]
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.7)


def test_op_and_program_times():
    r = raw()
    r.texts = {"paged_decode_attention": {"%k.1 = bf16[4097,8,16,128]"}}
    s = xtrace.reduce(r)
    assert s.ops_matching("[4097,8,16,128]") == pytest.approx(0.5)
    assert s.op_seconds["fusion"] == pytest.approx(1.5)
    assert s.ops_matching("paged_decode_attention") == pytest.approx(0.5)
    assert "outside" not in s.op_seconds           # after the window
    assert sorted(s.modules_named("jit_tick")) == pytest.approx([1.0, 2.0])
    assert s.modules_named("jit_prefill") == []


def test_idle_gaps_go_to_the_innermost_host_span():
    s = xtrace.reduce(raw())
    # gap [2, 5]: midpoint 3.5 in bench.step; gap [6, 10]: midpoint 8 in
    # bench.wait
    assert s.idle_by_span == pytest.approx({"bench.step": 3.0,
                                            "bench.wait": 4.0})
    b = s.breakdown()
    assert b["idle_gaps"][0] == ["bench.wait", pytest.approx(4.0)]
    assert b["device_ops"][0][0] == "fusion"


def test_gap_outside_any_span_is_host_other():
    r = raw()
    r.host = [I("bench.window", 0.0, 10.0)]
    s = xtrace.reduce(r)
    assert set(s.idle_by_span) == {xtrace.OTHER}


def test_busy_is_averaged_over_chips():
    r = raw()
    r.ops["/device:TPU:1"] = [I("fusion", 0.0, 10.0)]
    s = xtrace.reduce(r)
    assert s.chips == 2
    assert s.busy_s == pytest.approx((3.0 + 10.0) / 2)


def test_overlapping_events_of_one_op_raise():
    r = raw()
    r.ops["/device:TPU:0"] += [I("copy", 0.0, 10.0), I("copy", 0.0, 10.0)]
    with pytest.raises(ValueError):
        xtrace.reduce(r)


def test_no_window_span_raises():
    r = raw()
    r.host = []
    with pytest.raises(ValueError):
        xtrace.reduce(r)


@pytest.mark.parametrize("text,want", [
    ("%copy.169 = bf16[32,16,2048,64]{3,1,2,0:T(8,128)(2,1)} copy(bf16[32,"
     "16,2048,64]{2,3,1,0:T(8,128)(2,1)} %caches_0___mixer____k__.1)",
     "copy_bf16_32_16_2048_64"),
    ("%fusion.12 = (bf16[8,128]{1,0}, f32[8]{0}) fusion(%p0), kind=kLoop",
     "fusion_bf16_8_128"),
    ("%custom-call.3 = bf16[128,8,128]{2,1,0} custom-call(%a), "
     'custom_call_target="tpu_custom_call", kernel_name="_paged_decode_'
     'kernel"', "_paged_decode_kernel"),
    ("%branch_0_fun.7 = bf16[128,8,128]{2,1,0:T(8,128)(2,1)S(1)} custom-"
     "call(s32[16,256]{1,0} %a, bf16[4097,8,16,128]{3,2,1,0} %b), "
     'custom_call_target="tpu_custom_call", frontend_attributes={}',
     "tpu_custom_call_bf16_128_8_128"),
    ("%constant.2 = s32[] constant(0)", "constant_s32"),
    ("fusion.3", "fusion"),
])
def test_op_label(text, want):
    assert xtrace.op_label(text) == want
