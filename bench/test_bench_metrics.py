"""Metric readers on a recorded run: steps, requests and a reduced trace
written out by hand."""
import json
from pathlib import Path

import pytest

from bench import harness, xtrace
from bench.harness import ReqRecord, Run, StepRecord

BENCH = Path(__file__).resolve().parent
M = harness._load_module(BENCH / "configs/qwen_dense.py").dims(
    json.loads((BENCH / "configs/qwen1.5-110b-pp20.json").read_text()))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return harness._load_module(BENCH / "metrics" / f"{name}.py").read


def summary(kernel_s, tick_s=(0.04, 0.04)):
    pool = "bf16[4097,8,16,128]"
    return xtrace.Summary(
        window=(0.0, 1.0), busy_s=0.9,
        op_seconds={"tpu_custom_call_bf16_128_8_128": kernel_s,
                    "fusion_f32_16": 0.3},
        op_texts={"tpu_custom_call_bf16_128_8_128": {
            f'%b.1 = bf16[128,8,128] custom-call(s32[16,256] %t, {pool} %k)'
            ', custom_call_target="tpu_custom_call"'}},
        module_seconds={"jit_tick": list(tick_s), "jit_prefill": [0.01]},
        idle_by_span={"bench.step": 0.1}, chips=1)


def run(trace, steps, requests=(), seconds=1.0):
    return Run(cell=None, model=M, serve={"block_size": 16},
               seconds=seconds, setup_s=20.0, requests=list(requests),
               steps=list(steps), refactors=[{"t": 2e-5}], controller=True,
               trace=trace, peaks=PEAKS)


STEPS = [StepRecord(0.0, 0.04, 16, 16 * 1000, 16 * 63, 300, 1),
         StepRecord(0.04, 0.08, 16, 16 * 1001, 16 * 63, 0, 0)]


def test_paged_roofline_by_hand():
    # bytes: 4 layers x (2 x 1008 blocks x 16 rows x 8 x 128 x 2 x 2 bytes
    # + 32 tokens x 64 x 128 x 2 x 2); flops: 4 x 4 x 64 x 128 x 32,016
    nbytes = 4 * (2 * 1008 * 16 * 8 * 128 * 4 + 32 * 64 * 128 * 4)
    ops = 4 * 4 * 64 * 128 * 32016
    least = max(nbytes / 819e9, ops / 197e12)
    got = reader("paged_attn_roofline")(run(summary(0.02), STEPS))
    assert got == pytest.approx(100 * least / 0.02)


def test_paged_roofline_over_100_raises():
    with pytest.raises(ValueError):
        reader("paged_attn_roofline")(run(summary(1e-6), STEPS))


def test_paged_roofline_without_the_kernel_reads_nothing():
    s = summary(0.02)
    s.op_texts = {}
    assert reader("paged_attn_roofline")(run(s, STEPS)) is None


def test_tick_mfu_idle_and_prefill():
    r = run(summary(0.02), STEPS)
    assert reader("decode_tick_ms")(r) == pytest.approx(40.0)
    assert reader("device_idle_share")(r) == pytest.approx(10.0)
    assert reader("prefill_ms_per_ktok")(r) == pytest.approx(
        1e6 * 0.01 / 300)
    from bench import flops
    ops = flops.decode_flops(M, 32, 16 * 2001)
    assert reader("decode_step_mfu")(r) == pytest.approx(
        100 * ops / 197e12 / 0.08)
    assert reader("refactor_stall_ms")(r) == pytest.approx(0.02)


def test_host_clock_metrics():
    reqs = [ReqRecord(0, 0.1, None, 3, stamps=[0.3, 0.4, 0.6]),
            ReqRecord(1, 0.5, None, 2, stamps=[0.6, 2.0]),
            ReqRecord(2, -1e9, None, 2, stamps=[-0.5, 0.2])]
    r = run(None, [], reqs, seconds=1.0)
    assert reader("output_tokens_per_s")(r) == pytest.approx(5.0)
    # first tokens of requests due in the window: 0.2 and 0.1 s
    assert reader("ttft_p95_ms")(r) == pytest.approx(195.0)
    # gaps with both tokens in the window: 0.1 and 0.2 s
    assert reader("itl_p95_ms")(r) == pytest.approx(195.0)
    assert reader("setup_s")(r) == 20.0
    assert reader("decode_tick_ms")(r) is None
