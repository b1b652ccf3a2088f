"""Operations and bytes the served algorithm needs, computed from shapes.

``m`` is the size dict of a configuration (``dims`` of its architecture
module): d, H, Kh, hd, ff, L, V.  A multiply-add counts as two operations.
Counts are of the work the algorithm needs, not of what a kernel happens to
do: padding, masked rows and recomputation are not counted.
"""
from __future__ import annotations


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies in one layer: q, k, v, o and the three
    SwiGLU projections (norm scales and biases are not matmuls)."""
    d, H, Kh, hd, ff = m["d"], m["H"], m["Kh"], m["hd"], m["ff"]
    return d * H * hd + 2 * d * Kh * hd + H * hd * d + 3 * d * ff


def head_params(m: dict) -> int:
    return m["d"] * m["V"]


def attention_flops(m: dict, ctx: int) -> int:
    """One query token against ``ctx`` cached tokens, one layer: q.k over
    every head, then the weighted sum of v."""
    return 4 * m["H"] * m["hd"] * ctx


def decode_flops(m: dict, n_tokens: int, ctx_sum: int) -> int:
    """Model operations of ``n_tokens`` decoded tokens whose attended
    context lengths add up to ``ctx_sum``: 2 x the matmul weights of every
    layer and of the head per token, plus attention over the live context."""
    per_token = 2 * (m["L"] * layer_matmul_params(m) + head_params(m))
    return n_tokens * per_token + m["L"] * 4 * m["H"] * m["hd"] * ctx_sum


def paged_attention_flops(m: dict, ctx_sum: int) -> int:
    """The table-walk kernel's operations over all layers of a tick."""
    return m["L"] * 4 * m["H"] * m["hd"] * ctx_sum


def paged_attention_bytes(m: dict, n_tokens: int, live_blocks: int,
                          block_size: int, itemsize: int = 2) -> int:
    """Bytes the table-walk kernel must move over all layers of a tick:
    the k and v rows of every live block of the decoding slots, the
    queries read and the outputs written."""
    kv = live_blocks * block_size * m["Kh"] * m["hd"] * 2 * itemsize
    qo = n_tokens * m["H"] * m["hd"] * 2 * itemsize
    return m["L"] * (kv + qo)


def blocks(ctx: int, block_size: int) -> int:
    return -(-ctx // block_size)


def roofline_share(least_s: float, actual_s: float) -> float:
    """The least time over the time taken, in %.  Above 100 the operations
    or bytes are counted too high, or the time leaves out part of the
    work: that is a fault of the count, never a reading."""
    share = 100.0 * least_s / actual_s
    if share > 100.0:
        raise ValueError(f"share of peak {share:.1f}% is above 100%: the "
                         "count of operations or bytes, or the time, is "
                         "wrong")
    return share
