"""Core layers: norms, rotary embeddings, attention variants, MLPs, MoE.

All layers are pure functions over plain-dict param pytrees.  Shapes are read
from the params (not the config) so the same code runs on full tensors and on
tensor-parallel shards inside ``shard_map`` (heads / ff sliced per device).

Conventions
-----------
- activations: ``(batch, seq, d_model)``
- attention weights: ``wq (d, H, hd)``, ``wk/wv (d, Kh, hd)``, ``wo (H, hd, d)``
- KV cache: ``k/v (batch, Kh, max_seq, hd)`` (head-major for decode reads)
- ``tp_axis``: name of the tensor-parallel mesh axis (None outside shard_map);
  output projections psum over it.
- every apply returns ``(y, aux)`` where ``aux`` is a scalar auxiliary loss
  (MoE load balancing; 0 elsewhere).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MLAConfig, MoEConfig

Params = dict
f32 = jnp.float32


def _maybe_psum(x, tp_axis):
    return jax.lax.psum(x, tp_axis) if tp_axis else x


def cast_like(new_tree, old_tree):
    """Cast new cache leaves to the old cache's dtypes (pytree-stable jit)."""
    if old_tree is None or new_tree is None:
        return new_tree
    return jax.tree.map(lambda n, o: n.astype(o.dtype), new_tree, old_tree)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(params: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(f32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(f32)).astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=f32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, hd); positions: (seq,) or (batch, seq) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    angles = positions.astype(f32)[..., :, None] * freqs   # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]             # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(f32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, sliding window, chunked/flash formulation)
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    d, H, Kh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": jax.random.normal(k1, (d, H, hd), dtype) * s,
        "wk": jax.random.normal(k2, (d, Kh, hd), dtype) * s,
        "wv": jax.random.normal(k3, (d, Kh, hd), dtype) * s,
        "wo": jax.random.normal(k4, (H, hd, d), dtype) * (s / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dtype)
        p["bk"] = jnp.zeros((Kh, hd), dtype)
        p["bv"] = jnp.zeros((Kh, hd), dtype)
    return p


def _qkv(params: Params, x: jax.Array):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    return q, k, v


def flash_attention_jnp(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        q_offset: int = 0, kv_block: int = 1024,
                        scale: Optional[float] = None) -> jax.Array:
    """Memory-efficient attention: online softmax over KV blocks via lax.scan.

    q: (B, Sq, H, hd);  k/v: (B, Skv, Kh, hd) with H = Kh * G.
    ``q_offset``: absolute position of q[0] relative to k[0] (for decode /
    chunked prefill).  ``window``: sliding window size (0 = unwindowed).
    """
    B, Sq, H, hd = q.shape
    Skv, Kh = k.shape[1], k.shape[2]
    hdv = v.shape[-1]                  # may differ from hd (MLA)
    G = H // Kh
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    blk = min(kv_block, Skv)
    nblk = math.ceil(Skv / blk)
    pad = nblk * blk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qf = (q.astype(f32) * scale).reshape(B, Sq, Kh, G, hd)
    kb = k.reshape(B, nblk, blk, Kh, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, blk, Kh, hdv).transpose(1, 0, 2, 3, 4)

    q_pos = q_offset + jnp.arange(Sq)

    def body(carry, inp):
        m, l, acc = carry
        bidx, kblk, vblk = inp
        kv_pos = bidx * blk + jnp.arange(blk)
        s = jnp.einsum("bqhgk,bjhk->bqhgj", qf, kblk.astype(f32))
        mask = kv_pos[None, :] < Skv  # padding
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= kv_pos[None, :] > (q_pos[:, None] - window)
        s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, :, None, None, :], p, 0.0)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bqhgj,bjhk->bqhgk", p, vblk.astype(f32))
        return (m_new, l, acc), None

    m0 = jnp.full((B, Sq, Kh, G), -jnp.inf, f32)
    l0 = jnp.zeros((B, Sq, Kh, G), f32)
    a0 = jnp.zeros((B, Sq, Kh, G, hdv), f32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (jnp.arange(nblk), kb, vb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Sq, H, hdv).astype(q.dtype)


def decode_attention_jnp(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         cache_len: jax.Array, *, window: int = 0,
                         scale: Optional[float] = None) -> jax.Array:
    """Single-token decode attention against a head-major cache.

    q: (B, 1, H, hd); k_cache/v_cache: (B, Kh, Smax, hd); cache_len: scalar —
    number of valid cache entries; the query attends to [0, cache_len).
    """
    B, _, H, hd = q.shape
    Kh, Smax = k_cache.shape[1], k_cache.shape[2]
    G = H // Kh
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qf = (q.astype(f32) * scale).reshape(B, Kh, G, hd)
    s = jnp.einsum("bhgk,bhjk->bhgj", qf, k_cache.astype(f32))
    pos = jnp.arange(Smax)
    cl = jnp.asarray(cache_len)
    if cl.ndim == 1:                       # ragged: per-request cache length
        mask = pos[None, :] < cl[:, None]
        if window:
            mask |= (cl[:, None] >= Smax)
        mask = mask[:, None, None, :]      # (B,1,1,Smax)
    else:
        mask = pos[None, :] < cl
        if window:
            mask |= (cl >= Smax)
        mask = mask[None, None, :, :] if mask.ndim == 2 else mask[None, None, None, :]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgj,bhjk->bhgk", p, v_cache.astype(f32))
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def _write_kv_rows(leaf: jax.Array, idx: jax.Array, offs: jax.Array,
                   new: jax.Array) -> jax.Array:
    """Write one row per (entry, KV head) into a head-major cache leaf.

    leaf: ``(A, Kh, L, hd)``, a dense cache (A = batch, L = max_seq) or a
    block pool (A = blocks, L = block size); idx, offs: ``(n,)`` leading
    index and row of each entry; new: ``(n, Kh, hd)``.  The leaf is viewed
    as ``(A * Kh, L, hd)`` (merging the two major dims is a bitcast) and
    written with one ``(row, offset)`` index per entry and head, so the
    scatter's window is ``hd`` alone.  ``leaf.at[idx, :, offs, :]`` writes
    the same values, but its ``(Kh, hd)`` window straddles the indexed
    dim, and on a TPU XLA then copies the whole leaf into another layout
    and back on every call.  An entry whose offset lies past ``L`` is
    dropped, as there.
    """
    A, Kh, L, hd = leaf.shape
    rows = (idx[:, None] * Kh + jnp.arange(Kh)).reshape(-1)
    offs = jnp.broadcast_to(offs[:, None], (idx.shape[0], Kh)).reshape(-1)
    flat = leaf.reshape(A * Kh, L, hd).at[rows, offs].set(
        new.reshape(-1, hd).astype(leaf.dtype))
    return flat.reshape(leaf.shape)


def _paged_attention(q, k, v, cache, block_table, *, pos0, wo, kv_block,
                     causal, paged_kernel, kv_extent=0):
    """Attention over the paged layout: pools + per-slot block tables.

    Decode (S == 1) writes the new token into each slot's tail block and
    attends over the table; idle slots (all-null tables) scatter into the
    null block 0, which no masked read ever observes.  Prefill (S > 1,
    batch 1 — the engine's per-slot prefill) scatters the whole prompt
    through the table; flash attention runs on the fresh k/v and never
    reads the pool, matching the dense path exactly.  Chunked prefill
    (S > 1 with ``kv_extent`` set) additionally gathers the logical view
    so chunk n attends over chunks 0..n already resident in the pool; the
    reduction extent is pinned to ``kv_extent`` so outputs stay
    bit-identical to a whole-prompt prefill bucketed at that extent
    (garbage rows past the written prefix are causally masked to exact
    zeros).  Paged layouts are global-attention only (``can_page``), so
    there is no window handling.
    """
    from repro.kernels.decode_attention import paged_decode_attention

    B, S, H, hd = q.shape
    bs = cache["k"].shape[2]
    M = block_table.shape[1]
    with jax.named_scope("kv_write"):
        km = jnp.moveaxis(k, 1, 2).astype(cache["k"].dtype)  # (B, Kh, S, hd)
        vm = jnp.moveaxis(v, 1, 2).astype(cache["v"].dtype)
    bt = jnp.asarray(block_table)

    if S == 1:
        p0 = jnp.broadcast_to(jnp.asarray(pos0).reshape(-1), (B,))
        with jax.named_scope("kv_write"):
            pid = bt[jnp.arange(B), p0 // bs]
            off = p0 % bs
            # idle slots all write into the null block 0: which of the
            # duplicate rows lands there is unspecified and harmless, since
            # no masked read ever sees block 0
            kc = _write_kv_rows(cache["k"], pid, off, km[:, :, 0, :])
            vc = _write_kv_rows(cache["v"], pid, off, vm[:, :, 0, :])
        if paged_kernel:
            out = paged_decode_attention(q[:, 0], kc, vc, bt,
                                         p0 + 1)[:, None]
        else:
            # gather the logical (B, Kh, M*bs, hd) view — identical in
            # shape and masking to a dense Smax = M*bs cache, so decode
            # outputs are bit-identical to the dense layout
            gk = jnp.moveaxis(kc[bt], 2, 1).reshape(B, -1, M * bs, hd)
            gv = jnp.moveaxis(vc[bt], 2, 1).reshape(B, -1, M * bs,
                                                    vc.shape[-1])
            out = decode_attention_jnp(q, gk, gv, cache_len=p0 + 1)
    else:
        pos = jnp.asarray(pos0).reshape(-1)[:1] + jnp.arange(S)
        with jax.named_scope("kv_write"):
            pids = bt[0, pos // bs]
            offs = pos % bs
            kc = _write_kv_rows(cache["k"], pids, offs,
                                jnp.moveaxis(km[0], 0, 1))
            vc = _write_kv_rows(cache["v"], pids, offs,
                                jnp.moveaxis(vm[0], 0, 1))
        if kv_extent:
            # chunked prefill: attend over the slot's logical view so this
            # chunk's queries see all previously committed chunks
            p0 = jnp.asarray(pos0).reshape(-1)[0]
            gk = jnp.moveaxis(kc[bt], 2, 1).reshape(B, -1, M * bs, hd)
            gv = jnp.moveaxis(vc[bt], 2, 1).reshape(B, -1, M * bs,
                                                    vc.shape[-1])
            out = flash_attention_jnp(
                q, jnp.moveaxis(gk[:, :, :kv_extent], 1, 2),
                jnp.moveaxis(gv[:, :, :kv_extent], 1, 2),
                causal=causal, q_offset=p0, kv_block=kv_block)
        else:
            out = flash_attention_jnp(q, k, v, causal=causal, q_offset=0,
                                      kv_block=kv_block)
    y = jnp.einsum("bshk,hkd->bsd", out, wo)
    return y, {"k": kc, "v": vc}


def apply_attention(cfg: ModelConfig, params: Params, x: jax.Array, *,
                    pos0, cache=None, is_global: bool = True, causal: bool = True,
                    tp_axis: Optional[str] = None, kv_block: int = 1024,
                    sp_axis: Optional[str] = None, block_table=None,
                    paged_kernel: bool = False, kv_extent: int = 0):
    """Self attention; prefill (cache is None or being filled) or decode.

    pos0: int32 scalar — absolute position of x[:, 0].
    cache: None (training / stateless prefill) or dict(k, v, head-major).
    sp_axis: sequence-parallel decode — global-attention caches have their
    seq dim sharded over this mesh axis (long-context decode).
    block_table: paged KV — cache leaves are block POOLS ``(n_blocks, Kh,
    block_size, hd)`` shared across the batch and ``block_table`` is the
    ``(B, max_logical_blocks)`` map from each slot's logical blocks to
    physical ids (0 = null block).  ``paged_kernel`` selects the Pallas
    block-walk kernel over the gather path (gather reconstructs the dense
    logical view, so its outputs are bit-identical to the dense layout).
    kv_extent: chunked prefill — S > 1 tokens are written at ``pos0`` and
    attend over cache rows [0, kv_extent) (earlier chunks + this one, with
    garbage past the written prefix causally masked to exact zeros) rather
    than over the fresh tokens alone.  Pinning the reduction extent keeps
    greedy outputs bit-identical to a whole-prompt prefill bucketed at
    ``kv_extent``.
    Returns (y, new_cache, aux).
    """
    B, S, _ = x.shape
    window = 0 if is_global else cfg.sliding_window
    q, k, v = _qkv(params, x)
    if cfg.rope_theta:
        p0 = jnp.asarray(pos0)
        positions = (p0[:, None] if p0.ndim == 1 else p0) + jnp.arange(S)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if block_table is not None and cache is not None:
        y, new_cache = _paged_attention(
            q, k, v, cache, block_table, pos0=pos0, wo=params["wo"],
            kv_block=kv_block, causal=causal, paged_kernel=paged_kernel,
            kv_extent=kv_extent)
        y = _maybe_psum(y, tp_axis)
        return y, new_cache, jnp.zeros((), f32)

    use_sp = sp_axis is not None and not window and S == 1 and cache is not None
    if use_sp:
        km = jnp.moveaxis(k, 1, 2)
        vm = jnp.moveaxis(v, 1, 2)
        with jax.named_scope("kv_write"):
            new_cache = {"k": sp_cache_write(cache["k"], km, pos0, sp_axis),
                         "v": sp_cache_write(cache["v"], vm, pos0, sp_axis)}
        out = sp_decode_attention(q, new_cache["k"], new_cache["v"], pos0, sp_axis)
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
        y = _maybe_psum(y, tp_axis)
        return y, new_cache, jnp.zeros((), f32)

    new_cache = None
    if cache is not None:
        with jax.named_scope("kv_write"):
            Smax = cache["k"].shape[2]
            # (B, Kh, S, hd)
            km = jnp.moveaxis(k, 1, 2).astype(cache["k"].dtype)
            vm = jnp.moveaxis(v, 1, 2).astype(cache["v"].dtype)
            pos_vec = jnp.asarray(pos0).ndim == 1
            if S == 1 and pos_vec:
                # ragged decode: per-request write slots (continuous
                # batching)
                slots = jnp.mod(pos0, Smax) if window else pos0
                bi = jnp.arange(B)
                kc = _write_kv_rows(cache["k"], bi, slots, km[:, :, 0, :])
                vc = _write_kv_rows(cache["v"], bi, slots, vm[:, :, 0, :])
            elif S == 1:
                start = jnp.mod(pos0, Smax) if window else pos0
                kc = jax.lax.dynamic_update_slice(cache["k"], km,
                                                  (0, 0, start, 0))
                vc = jax.lax.dynamic_update_slice(cache["v"], vm,
                                                  (0, 0, start, 0))
            elif kv_extent:
                # chunked prefill: commit this chunk's rows at pos0 (the
                # engine guarantees pos0 + S <= Smax)
                kc = jax.lax.dynamic_update_slice(cache["k"], km,
                                                  (0, 0, pos0, 0))
                vc = jax.lax.dynamic_update_slice(cache["v"], vm,
                                                  (0, 0, pos0, 0))
            elif S >= Smax:
                # prefill larger than ring: keep the last Smax tokens,
                # placed so that token at absolute position p sits at slot
                # p % Smax
                km, vm = km[:, :, -Smax:], vm[:, :, -Smax:]
                shift = S % Smax
                kc = jnp.roll(km, shift, axis=2)
                vc = jnp.roll(vm, shift, axis=2)
            else:
                kc = jax.lax.dynamic_update_slice(cache["k"], km,
                                                  (0, 0, 0, 0))
                vc = jax.lax.dynamic_update_slice(cache["v"], vm,
                                                  (0, 0, 0, 0))
            new_cache = {"k": kc, "v": vc}

    if S == 1 and cache is not None:
        out = decode_attention_jnp(q, new_cache["k"], new_cache["v"],
                                   cache_len=pos0 + 1, window=window)
    elif kv_extent and cache is not None:
        # chunked prefill: attend over all committed chunks 0..n, extent
        # pinned at kv_extent for bit-exactness vs whole-prompt prefill
        out = flash_attention_jnp(
            q, jnp.moveaxis(new_cache["k"][:, :, :kv_extent], 1, 2),
            jnp.moveaxis(new_cache["v"][:, :, :kv_extent], 1, 2),
            causal=causal, window=window, q_offset=pos0, kv_block=kv_block)
    else:
        out = flash_attention_jnp(q, k, v, causal=causal, window=window,
                                  q_offset=0, kv_block=kv_block)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    y = _maybe_psum(y, tp_axis)
    if tp_axis is not None and params["wq"].shape[-2] == cfg.n_heads:
        # heads not shardable at this T: every rank computed all heads —
        # normalize the psum overcount (small models on wide tensor axes)
        y = y / jax.lax.psum(1, tp_axis)
    return y, new_cache, jnp.zeros((), f32)


def sp_decode_attention(q: jax.Array, k_loc: jax.Array, v_loc: jax.Array,
                        pos, axis: str, scale: Optional[float] = None):
    """Sequence-parallel decode attention (flash-decode across devices).

    The KV cache's sequence dim is sharded over mesh axis ``axis``; each
    device computes partial attention over its shard and the results combine
    with an LSE-weighted psum.  q: (B,1,H,hd); k_loc/v_loc: (B,Kh,Sloc,hd).
    """
    B, _, H, hd = q.shape
    Kh, Sloc = k_loc.shape[1], k_loc.shape[2]
    G = H // Kh
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    r = jax.lax.axis_index(axis)
    qf = (q.astype(f32) * scale).reshape(B, Kh, G, hd)
    s = jnp.einsum("bhgk,bhjk->bhgj", qf, k_loc.astype(f32))
    gpos = r * Sloc + jnp.arange(Sloc)
    mask = gpos[None, None, None, :] <= pos
    s = jnp.where(mask, s, -jnp.inf)
    m_loc = s.max(axis=-1)
    m = jax.lax.pmax(m_loc, axis)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.where(mask, jnp.exp(s - m_safe[..., None]), 0.0)
    l = jax.lax.psum(p.sum(axis=-1), axis)
    o = jax.lax.psum(jnp.einsum("bhgj,bhjk->bhgk", p, v_loc.astype(f32)), axis)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def sp_cache_write(cache_leaf: jax.Array, update: jax.Array, pos, axis: str):
    """Write one decode token into a sequence-sharded cache (B,Kh,Sloc,hd).

    Only the shard owning global slot ``pos`` performs the write.
    """
    Sloc = cache_leaf.shape[2]
    r = jax.lax.axis_index(axis)
    owner = pos // Sloc
    slot = jnp.where(r == owner, pos - owner * Sloc, 0)
    old = jax.lax.dynamic_slice(cache_leaf, (0, 0, slot, 0),
                                (cache_leaf.shape[0], cache_leaf.shape[1], 1,
                                 cache_leaf.shape[3]))
    upd = jnp.where(r == owner, update.astype(cache_leaf.dtype), old)
    return jax.lax.dynamic_update_slice(cache_leaf, upd, (0, 0, slot, 0))


# ---------------------------------------------------------------------------
# Cross attention (VLM image layers, whisper decoder)
# ---------------------------------------------------------------------------

def init_cross_attention(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    p = init_attention(key, cfg, dtype)
    p["gate"] = jnp.zeros((), dtype)        # tanh-gated residual (llama-vision)
    return p


def apply_cross_attention(cfg: ModelConfig, params: Params, x: jax.Array, *,
                          memory: Optional[jax.Array] = None, cache=None,
                          tp_axis: Optional[str] = None):
    """Cross attention to ``memory`` tokens (B, M, d) — precomputed frontend.

    KV may come precomputed from ``cache`` (dict k,v head-major) so decode
    steps don't recompute projections.  Returns (y, new_cache, aux).
    """
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if "bq" in params:
        q = q + params["bq"]
    if cache is not None and memory is None:
        k_hm, v_hm = cache["k"], cache["v"]
    else:
        k = jnp.einsum("bmd,dhk->bmhk", memory, params["wk"])
        v = jnp.einsum("bmd,dhk->bmhk", memory, params["wv"])
        if "bk" in params:
            k, v = k + params["bk"], v + params["bv"]
        k_hm, v_hm = jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2)
    new_cache = {"k": k_hm, "v": v_hm}
    M = k_hm.shape[2]
    out = decode_attention_jnp(q, k_hm, v_hm, cache_len=M) if q.shape[1] == 1 else \
        flash_attention_jnp(q, jnp.moveaxis(k_hm, 1, 2), jnp.moveaxis(v_hm, 1, 2),
                            causal=False, q_offset=0)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    y = y * jnp.tanh(params["gate"].astype(f32)).astype(y.dtype)
    y = _maybe_psum(y, tp_axis)
    if tp_axis is not None and params["wq"].shape[-2] == cfg.n_heads:
        y = y / jax.lax.psum(1, tp_axis)
    return y, new_cache, jnp.zeros((), f32)


# ---------------------------------------------------------------------------
# Multi-head Latent Attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    m: MLAConfig = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 8)
    s = 1.0 / math.sqrt(d)
    sl = 1.0 / math.sqrt(m.kv_lora_rank)
    sq = 1.0 / math.sqrt(m.q_lora_rank)
    return {
        "wq_down": jax.random.normal(ks[0], (d, m.q_lora_rank), dtype) * s,
        "wq_up": jax.random.normal(ks[1], (m.q_lora_rank, H, m.nope_head_dim + m.rope_head_dim), dtype) * sq,
        "wkv_down": jax.random.normal(ks[2], (d, m.kv_lora_rank + m.rope_head_dim), dtype) * s,
        "wk_up": jax.random.normal(ks[3], (m.kv_lora_rank, H, m.nope_head_dim), dtype) * sl,
        "wv_up": jax.random.normal(ks[4], (m.kv_lora_rank, H, m.v_head_dim), dtype) * sl,
        "wo": jax.random.normal(ks[5], (H, m.v_head_dim, d), dtype) * (s / math.sqrt(2 * cfg.n_layers)),
        "q_norm": jnp.ones((m.q_lora_rank,), dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
    }


def apply_mla(cfg: ModelConfig, params: Params, x: jax.Array, *,
              pos0, cache=None, tp_axis: Optional[str] = None,
              kv_block: int = 1024):
    """MLA: latent-compressed KV. Prefill materializes K/V per chunk; decode
    uses the absorbed (MQA-like) form over the latent cache.

    cache: dict(latent (B, Smax, r), k_rope (B, Smax, rd)).
    """
    m: MLAConfig = cfg.mla
    B, S, _ = x.shape
    H = params["wq_up"].shape[1]            # local heads under TP
    nd, rd, vd = m.nope_head_dim, m.rope_head_dim, m.v_head_dim

    ql = rms_norm({"scale": params["q_norm"]},
                  jnp.einsum("bsd,dr->bsr", x, params["wq_down"]), cfg.rms_eps)
    q = jnp.einsum("bsr,rhk->bshk", ql, params["wq_up"])
    q_nope, q_rope = q[..., :nd], q[..., nd:]

    kv = jnp.einsum("bsd,dr->bsr", x, params["wkv_down"])
    latent = rms_norm({"scale": params["kv_norm"]}, kv[..., :m.kv_lora_rank], cfg.rms_eps)
    k_rope = kv[..., m.kv_lora_rank:][:, :, None, :]   # (B,S,1,rd) shared head

    positions = pos0 + jnp.arange(S)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)[:, :, 0, :]

    new_cache = None
    if cache is not None:
        with jax.named_scope("kv_write"):
            lat = jax.lax.dynamic_update_slice(
                cache["latent"], latent.astype(cache["latent"].dtype),
                (0, pos0 if S == 1 else 0, 0))
            krc = jax.lax.dynamic_update_slice(
                cache["k_rope"], k_rope.astype(cache["k_rope"].dtype),
                (0, pos0 if S == 1 else 0, 0))
        new_cache = {"latent": lat, "k_rope": krc}

    scale = 1.0 / math.sqrt(nd + rd)
    if S == 1 and cache is not None:
        # absorbed decode: q_lat = q_nope @ wk_up  -> score vs latent cache
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope.astype(f32),
                           params["wk_up"].transpose(0, 1, 2).astype(f32))
        s_n = jnp.einsum("bshr,bjr->bshj", q_lat, new_cache["latent"].astype(f32))
        s_r = jnp.einsum("bshk,bjk->bshj", q_rope.astype(f32),
                         new_cache["k_rope"].astype(f32))
        sc = (s_n + s_r) * scale
        Smax = new_cache["latent"].shape[1]
        mask = jnp.arange(Smax)[None, None, None, :] <= pos0
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o_lat = jnp.einsum("bshj,bjr->bshr", p, new_cache["latent"].astype(f32))
        out = jnp.einsum("bshr,rhk->bshk", o_lat, params["wv_up"].astype(f32)).astype(x.dtype)
    else:
        # prefill: materialize k/v chunk-wise inside flash scan — here via
        # full materialization per kv_block through the flash helper by
        # building k/v lazily per block is folded into flash via precompute:
        k_nope = jnp.einsum("bsr,rhk->bshk", latent, params["wk_up"])
        v = jnp.einsum("bsr,rhk->bshk", latent, params["wv_up"])
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, rd))], axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = flash_attention_jnp(q_full, k_full, v, causal=True,
                                  q_offset=0, kv_block=kv_block, scale=scale)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    y = _maybe_psum(y, tp_axis)
    return y, new_cache, jnp.zeros((), f32)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None,
             dtype=jnp.float32) -> Params:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    s = 1.0 / math.sqrt(d)
    sf = 1.0 / math.sqrt(ff) / math.sqrt(2 * cfg.n_layers)
    if cfg.mlp_act == "gelu":               # whisper: plain 2-matrix MLP
        return {"w1": jax.random.normal(k1, (d, ff), dtype) * s,
                "w2": jax.random.normal(k2, (ff, d), dtype) * sf}
    return {"w_gate": jax.random.normal(k1, (d, ff), dtype) * s,
            "w_up": jax.random.normal(k2, (d, ff), dtype) * s,
            "w_down": jax.random.normal(k3, (ff, d), dtype) * sf}


def _act(cfg: ModelConfig, g: jax.Array) -> jax.Array:
    if cfg.mlp_act == "geglu":
        return jax.nn.gelu(g, approximate=True)
    return jax.nn.silu(g)


def apply_mlp(cfg: ModelConfig, params: Params, x: jax.Array, *,
              tp_axis: Optional[str] = None):
    if "w1" in params:                      # plain gelu MLP
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, params["w1"]))
        y = jnp.einsum("bsf,fd->bsd", h, params["w2"])
    else:
        g = jnp.einsum("bsd,df->bsf", x, params["w_gate"])
        u = jnp.einsum("bsd,df->bsf", x, params["w_up"])
        y = jnp.einsum("bsf,fd->bsd", _act(cfg, g) * u, params["w_down"])
    return _maybe_psum(y, tp_axis), None, jnp.zeros((), f32)


# ---------------------------------------------------------------------------
# Mixture of Experts (replicated-activation expert parallelism)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    mo: MoEConfig = cfg.moe
    d, fe = cfg.d_model, mo.d_expert
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    s = 1.0 / math.sqrt(d)
    sf = 1.0 / math.sqrt(fe) / math.sqrt(2 * cfg.n_layers)
    p = {
        "router": jax.random.normal(k1, (d, mo.n_experts), dtype) * s,
        "w_gate": jax.random.normal(k2, (mo.n_experts, d, fe), dtype) * s,
        "w_up": jax.random.normal(k3, (mo.n_experts, d, fe), dtype) * s,
        "w_down": jax.random.normal(k4, (mo.n_experts, fe, d), dtype) * sf,
    }
    if mo.n_shared:
        sub = jax.random.split(k5, 3)
        fs = mo.d_expert * mo.n_shared
        p["shared"] = {
            "w_gate": jax.random.normal(sub[0], (d, fs), dtype) * s,
            "w_up": jax.random.normal(sub[1], (d, fs), dtype) * s,
            "w_down": jax.random.normal(sub[2], (fs, d), dtype) * sf,
        }
    return p


def apply_moe(cfg: ModelConfig, params: Params, x: jax.Array, *,
              tp_axis: Optional[str] = None):
    """Top-k MoE with capacity-bounded one-hot dispatch (GShard style).

    Expert parallelism: experts are sharded over ``tp_axis`` (w_* leading dim
    is the LOCAL expert count); activations are replicated across it, each
    rank dispatches tokens to its local experts only and the standard output
    psum combines — no all-to-all required (DESIGN.md §3).

    Router logits are always computed over the GLOBAL expert count.
    """
    mo: MoEConfig = cfg.moe
    B, S, d = x.shape
    T = B * S
    E = mo.n_experts                        # global experts (router dim)
    E_loc = params["w_gate"].shape[0]       # local experts on this rank
    n_rank = E // E_loc
    rank = jax.lax.axis_index(tp_axis) if tp_axis else 0

    xt = x.reshape(T, d)
    logits = jnp.einsum("td,de->te", xt.astype(f32), params["router"].astype(f32))
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, mo.top_k)       # (T, K)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch style)
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), f32).at[topi.reshape(-1)].add(1.0) / (T * mo.top_k)
    aux = E * jnp.sum(me * ce)

    cap = int(math.ceil(T * mo.top_k / E * mo.capacity_factor))
    cap = max(cap, 4)
    # position of each (t, k) assignment within its expert queue
    onehot = jax.nn.one_hot(topi, E, dtype=f32)        # (T, K, E)
    flat = onehot.reshape(T * mo.top_k, E)
    pos = (jnp.cumsum(flat, axis=0) - flat).reshape(T, mo.top_k, E)
    pos = (pos * onehot).sum(-1)                       # (T, K)
    keep = pos < cap

    # local expert slice of the dispatch tensor
    e0 = rank * E_loc
    li = topi - e0
    in_rank = (li >= 0) & (li < E_loc) & keep
    # (T, E_loc, cap) dispatch & combine tensors
    d_onehot = jax.nn.one_hot(li, E_loc, dtype=f32) * in_rank[..., None].astype(f32)
    p_onehot = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=f32)
    dispatch = jnp.einsum("tke,tkc->tec", d_onehot, p_onehot)        # (T,E_loc,cap)
    combine = jnp.einsum("tke,tkc,tk->tec", d_onehot, p_onehot, topw.astype(f32))

    xe = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), xt)     # (E_loc,cap,d)
    g = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", _act(cfg, g) * u, params["w_down"])
    y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), ye).reshape(B, S, d)

    if mo.n_shared:
        sh = params["shared"]
        g = jnp.einsum("bsd,df->bsf", x, sh["w_gate"])
        u = jnp.einsum("bsd,df->bsf", x, sh["w_up"])
        y = y + jnp.einsum("bsf,fd->bsd", _act(cfg, g) * u, sh["w_down"])

    y = _maybe_psum(y, tp_axis)
    if tp_axis:
        aux = jax.lax.psum(aux, tp_axis) / jax.lax.psum(1, tp_axis)
    return y, None, aux
