"""Flash-decode Pallas TPU kernels: one query token against a long KV cache.

Decode attention is HBM-bandwidth bound (the roofline's memory term for
decode_32k/long_500k): the kernels stream the cache through VMEM in blocks,
keeping the online-softmax state for all G query heads of one kv head in
scratch.  Grid = (batch·kv_heads, n_cache_blocks) — innermost sequential.

Two cache layouts:

* ``decode_attention`` — dense ``(B, Kh, Smax, hd)`` caches.  When
  ``Smax % block_k != 0`` the tail block simply runs past the array end:
  Pallas pads out-of-bounds reads and the ``cache_len`` mask (always
  ≤ Smax) discards them, so the hot path never copies the cache through
  ``jnp.pad``.
* ``paged_decode_attention`` — vLLM-style block pools ``(n_blocks, Kh,
  block_size, hd)`` plus per-slot block tables.  The grid walks each
  slot's *logical* blocks; a scalar-prefetched block table drives the
  BlockSpec index map, so each step DMAs exactly the physical block the
  slot owns — no dense ``Smax`` axis, no gather materialization.
  Unallocated table entries point at the null block 0 and sit beyond
  ``cache_len``, so the mask discards them.

``cache_len`` masking supports ragged batches (continuous batching engine);
both kernels take it by scalar prefetch, so it lives in SMEM.
``interpret=None`` compiles on TPU and interprets elsewhere (the CPU
validation path); see ``repro.kernels.call_kernel``.
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


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, bk: int, n_blocks: int,
                   kv_heads: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(f32) * scale            # (G, hd)
    k = k_ref[0].astype(f32)                    # (BK, hd)
    v = v_ref[0].astype(f32)                    # (BK, hdv)

    cache_len = len_ref[pl.program_id(0) // kv_heads]
    # out-of-bounds tail rows (Smax % bk != 0) hold unspecified data —
    # possibly NaN, which 0·NaN would leak through p @ v; zero them.
    vpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], 1), 0)
    v = jnp.where(vpos < cache_len, v, 0.0)

    s = q @ k.T                                  # (G, BK)
    pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = pos < cache_len
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + p @ v
    m_ref[...] = m_new

    @pl.when(ki == n_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     scale: float | None = None, block_k: int = 512,
                     interpret: bool | None = None):
    """q: (B, H, hd); caches: (B, Kh, Smax, hd/hdv); cache_len: scalar or (B,).

    Returns (B, H, hdv)."""
    B, H, hd = q.shape
    Kh, Smax = k_cache.shape[1], k_cache.shape[2]
    hdv = v_cache.shape[-1]
    G = H // Kh
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    # non-divisible Smax: the last grid step reads past the array end —
    # Pallas pads the out-of-bounds tail, and the cache_len mask (<= Smax
    # by contract) discards it.  No per-call jnp.pad copies of the cache.
    bk = min(block_k, Smax)
    nk = math.ceil(Smax / bk)

    qh = q.reshape(B * Kh, G, hd)
    kh = k_cache.reshape(B * Kh, Smax, hd)
    vh = v_cache.reshape(B * Kh, Smax, hdv)
    cl = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,))

    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk,
                               n_blocks=nk, kv_heads=Kh)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                  # per-slot cache lengths
        grid=(B * Kh, nk),
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda h, j, ln: (h, 0, 0)),
            # repro: noqa[PAL201] -- masked tail (pos/cache_len guard on k)
            pl.BlockSpec((1, bk, hd), lambda h, j, ln: (h, j, 0)),
            # repro: noqa[PAL201] -- masked tail (vpos zeroing guard on v)
            pl.BlockSpec((1, bk, hdv), lambda h, j, ln: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, hdv), lambda h, j, ln: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), f32),
            pltpu.VMEM((G, 1), f32),
            pltpu.VMEM((G, hdv), f32),
        ],
    )
    out = call_kernel(
        lambda interpret: pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B * Kh, G, hdv), q.dtype),
            interpret=interpret),
        cl, qh, kh, vh, interpret=interpret)
    return out.reshape(B, H, hdv)


# ---------------------------------------------------------------------------
# Paged flash-decode (block-table walk)
# ---------------------------------------------------------------------------

def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale: float, bs: int,
                         kv_heads: int, n_logical: int):
    h = pl.program_id(0)                        # batch*Kh row
    j = pl.program_id(1)                        # logical block of this slot

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cache_len = len_ref[h // kv_heads]

    # dead block: entirely past this slot's live length (incl. unallocated
    # table entries, which point at the null block).  Skip the matmul; the
    # DMA still happened, but correctness only needs the mask.
    @pl.when(j * bs < cache_len)
    def _compute():
        q = q_ref[0].astype(f32) * scale        # (G, hd)
        k = k_ref[0, 0].astype(f32)             # (bs, hd)
        v = v_ref[0, 0].astype(f32)             # (bs, hdv)
        s = q @ k.T                              # (G, bs)

        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = pos < cache_len
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + p @ v
        m_ref[...] = m_new

    @pl.when(j == n_logical - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_tables, cache_len, *,
                           scale: float | None = None,
                           interpret: bool | None = None):
    """Flash-decode over a paged KV cache.

    q: (B, H, hd); pools: (n_blocks, Kh, block_size, hd/hdv);
    block_tables: (B, max_logical_blocks) int32 physical ids (0 = null /
    unallocated); cache_len: scalar or (B,) live token counts.

    Grid = (B·Kh, max_logical_blocks); the scalar-prefetched block table
    drives the k/v BlockSpec index maps, so step (h, j) DMAs physical
    block ``block_tables[h // Kh, j]`` — cost proportional to the table
    width, never to a dense Smax axis.  Returns (B, H, hdv).
    """
    B, H, hd = q.shape
    Kh, bs = k_pool.shape[1], k_pool.shape[2]
    hdv = v_pool.shape[-1]
    G = H // Kh
    M = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    qh = q.reshape(B * Kh, G, hd)
    bt = jnp.asarray(block_tables, jnp.int32)
    cl = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,))

    kernel = functools.partial(_paged_decode_kernel, scale=scale, bs=bs,
                               kv_heads=Kh, n_logical=M)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # block table + cache lens
        grid=(B * Kh, M),
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda h, j, bt, ln: (h, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd),
                         lambda h, j, bt, ln: (bt[h // Kh, j], h % Kh, 0, 0)),
            pl.BlockSpec((1, 1, bs, hdv),
                         lambda h, j, bt, ln: (bt[h // Kh, j], h % Kh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, hdv), lambda h, j, bt, ln: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), f32),
            pltpu.VMEM((G, 1), f32),
            pltpu.VMEM((G, hdv), f32),
        ],
    )
    out = call_kernel(
        lambda interpret: pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B * Kh, G, hdv), q.dtype),
            interpret=interpret),
        bt, cl, qh, k_pool, v_pool, interpret=interpret)
    return out.reshape(B, H, hdv)
