"""Flash attention Pallas TPU kernel (prefill / training path).

TPU adaptation (DESIGN.md §2): blockwise online-softmax with explicit VMEM
tiling.  Grid = (batch·q_heads, n_q_blocks, n_kv_blocks); the innermost grid
axis is sequential on TPU, so the (m, l, acc) running state lives in VMEM
scratch and persists across kv blocks.  Block shapes are MXU-aligned
(multiples of 128 on the lane dim; q/kv block 128-512 rows keeps the working
set q(BQ,hd)+k(BK,hd)+v(BK,hd)+acc(BQ,hd) ≲ 1 MB in VMEM).

GQA folds the query-group into the q-head grid axis; the kv BlockSpec
index_map divides by the group size.  Sliding-window masking is fused
(window > 0) — on real TPU the pruned blocks are skipped via the grid
index_map; in this reference kernel they are masked.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import call_kernel

f32 = jnp.float32
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, bq: int, bk: int,
                  n_kv: int, seq_q: int, seq_kv: int, q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(f32) * scale          # (BQ, hd)
    k = k_ref[0].astype(f32)                  # (BK, hd)
    v = v_ref[0].astype(f32)                  # (BK, hdv)
    s = q @ k.T                                # (BQ, BK)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + q_offset                             # abs position of q row 0
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < seq_kv
    if causal:
        mask &= k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + p @ v
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None,
                    q_offset: int | None = None):
    """q: (B, Sq, H, hd); k/v: (B, Skv, Kh, hd/hdv). Returns (B, Sq, H, hdv).

    interpret=None compiles on TPU and interprets elsewhere
    (``repro.kernels.call_kernel``).
    q_offset: absolute position of q[:, 0] within the kv span; ``None``
    keeps the legacy END-alignment (q rows are the last Sq of Skv), which
    chunked prefill overrides with the chunk's start offset.
    """
    B, Sq, H, hd = q.shape
    Skv, Kh = k.shape[1], k.shape[2]
    hdv = v.shape[-1]
    G = H // Kh
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    nq = math.ceil(Sq / bq)
    nk = math.ceil(Skv / bk)
    pq = nq * bq - Sq
    pk = nk * bk - Skv
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v

    # layout: (B*H, S, hd) with kv indexed by h // G
    qh = qp.transpose(0, 2, 1, 3).reshape(B * H, nq * bq, hd)
    kh = kp.transpose(0, 2, 1, 3).reshape(B * Kh, nk * bk, hd)
    vh = vp.transpose(0, 2, 1, 3).reshape(B * Kh, nk * bk, hdv)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, n_kv=nk, seq_q=Sq, seq_kv=Skv,
        q_offset=(Skv - Sq) if q_offset is None else int(q_offset))

    out = call_kernel(lambda interpret: pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j, G=G: (h // G, j, 0)),
            pl.BlockSpec((1, bk, hdv), lambda h, i, j, G=G: (h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hdv), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, nq * bq, hdv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), f32),      # running max m
            pltpu.VMEM((bq, 1), f32),      # running sum l
            pltpu.VMEM((bq, hdv), f32),    # output accumulator
        ],
        interpret=interpret,
    ), qh, kh, vh, interpret=interpret)
    out = out.reshape(B, H, nq * bq, hdv)[:, :, :Sq].transpose(0, 2, 1, 3)
    return out
