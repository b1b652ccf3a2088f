"""Operation and byte counts of bench/flops.py against counts by hand."""
import json
from pathlib import Path

import pytest

from bench import flops
from bench.harness import _load_module

CONFIGS = Path(__file__).resolve().parent / "configs"
ARCH = _load_module(CONFIGS / "qwen_dense.py")


def dims(name):
    return ARCH.dims(json.loads((CONFIGS / f"{name}.json").read_text()))


# (config, matmul weights of a layer, head weights), by hand:
#   0.5b: q, o 1024 x 1024 each; k, v 1024 x 1024 each (16 KV heads of 64);
#         gate, up, down 1024 x 2816 each; head 1024 x 151936
#   110b: q, o 8192 x 8192 each; k, v 8192 x 1024 each (8 KV heads of 128);
#         gate, up, down 8192 x 49152 each; head 8192 x 19008
HAND = [
    ("qwen1.5-0.5b", 4 * 1024 * 1024 + 3 * 1024 * 2816, 1024 * 151936),
    ("qwen1.5-110b-pp20",
     2 * 8192 * 8192 + 2 * 8192 * 1024 + 3 * 8192 * 49152, 8192 * 19008),
]


@pytest.mark.parametrize("name,layer,head", HAND)
def test_matmul_weights(name, layer, head):
    m = dims(name)
    assert flops.layer_matmul_params(m) == layer
    assert flops.head_params(m) == head


@pytest.mark.parametrize("name,layer,head", HAND)
def test_decode_flops(name, layer, head):
    m = dims(name)
    # 3 tokens with contexts 10, 20 and 70: 2 x weights per token, and
    # q.k plus p.v, 2 x 2 x heads x head size per context row and layer
    want = 3 * 2 * (m["L"] * layer + head) \
        + m["L"] * 4 * m["H"] * m["hd"] * 100
    assert flops.decode_flops(m, 3, 100) == want
    assert flops.paged_attention_flops(m, 100) == \
        m["L"] * 4 * m["H"] * m["hd"] * 100


def test_decode_flops_per_token_by_hand():
    # 0.5b, one token at context 100:
    # 2 x (24 x 12,845,056 + 155,582,464) + 24 x 4 x 16 x 64 x 100
    assert flops.decode_flops(dims("qwen1.5-0.5b"), 1, 100) == 937_558_016


def test_paged_attention_bytes_by_hand():
    m = dims("qwen1.5-110b-pp20")
    # 2 decoding slots holding 10 live blocks of 16 rows between them:
    # k and v: 10 x 16 rows x 8 KV heads x 128 x 2 x 2 bytes = 655,360;
    # q and out: 2 x 64 heads x 128 x 2 x 2 bytes = 65,536; 4 layers
    assert flops.paged_attention_bytes(m, 2, 10, 16) == 4 * (655_360 + 65_536)


@pytest.mark.parametrize("ctx,bs,want", [(1, 16, 1), (16, 16, 1),
                                         (17, 16, 2), (0, 16, 0)])
def test_blocks(ctx, bs, want):
    assert flops.blocks(ctx, bs) == want


def test_roofline_share_over_100_raises():
    assert flops.roofline_share(0.5, 1.0) == 50.0
    with pytest.raises(ValueError):
        flops.roofline_share(1.2, 1.0)
