"""Block composition: config-driven layer stacks over heterogeneous mixers.

A *block* = pre-norm(mixer) + residual, then pre-norm(mlp) + residual
(RWKV owns its own two-residual structure).  Blocks are created per layer
index so the repeating pattern (DESIGN.md §5) decides the param tree.

``BlockCtx`` threads everything a block may need; unknown fields are ignored
by mixers that don't use them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (
    MIXER_ATTN, MIXER_CROSS, MIXER_MAMBA, MIXER_MLA, MIXER_RWKV,
    MLP_DENSE, MLP_MOE, LayerKind, ModelConfig)
from repro.models import layers as L
from repro.models import ssm as S

f32 = jnp.float32


@dataclass
class BlockCtx:
    pos0: Any = 0                      # int32 scalar: abs position of x[:,0]
    cache: Any = None                  # per-layer cache pytree or None
    memory: Any = None                 # (B, M, d) cross-attn memory tokens
    is_global: bool = True             # gemma local/global selector
    causal: bool = True                # False for encoder blocks
    tp_axis: Optional[str] = None
    sp_axis: Optional[str] = None      # sequence-parallel decode cache axis
    kv_block: int = 1024
    block_table: Any = None            # paged KV: (B, max_blocks) physical ids
    paged_kernel: bool = False         # Pallas block-walk vs gather decode
    kv_extent: int = 0                 # chunked prefill: attend over cache
                                       # rows [0, kv_extent) instead of the
                                       # fresh tokens only (0 = off)


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, kind: LayerKind, dtype=jnp.float32) -> dict:
    ks = iter(jax.random.split(key, 8))
    d = cfg.d_model
    p: dict = {"ln1": L.init_rmsnorm(d, dtype)}
    if kind.mixer == MIXER_ATTN:
        p["mixer"] = L.init_attention(next(ks), cfg, dtype)
    elif kind.mixer == MIXER_MLA:
        p["mixer"] = L.init_mla(next(ks), cfg, dtype)
    elif kind.mixer == MIXER_CROSS:
        p["mixer"] = L.init_cross_attention(next(ks), cfg, dtype)
    elif kind.mixer == MIXER_MAMBA:
        p["mixer"] = S.init_mamba(next(ks), cfg, dtype)
    elif kind.mixer == MIXER_RWKV:
        p["mixer"] = S.init_rwkv(next(ks), cfg, dtype)
        p["ln2"] = L.init_rmsnorm(d, dtype)
        return p                        # rwkv has no separate mlp
    else:
        raise ValueError(kind.mixer)
    if kind.extra_cross:
        p["cross"] = L.init_cross_attention(next(ks), cfg, dtype)
        p["ln_cross"] = L.init_rmsnorm(d, dtype)
    p["ln2"] = L.init_rmsnorm(d, dtype)
    p["mlp"] = (L.init_moe(next(ks), cfg, dtype) if kind.mlp == MLP_MOE
                else L.init_mlp(next(ks), cfg, dtype=dtype))
    return p


# ---------------------------------------------------------------------------
# Per-layer apply
# ---------------------------------------------------------------------------

_ATTENTION_MIXERS = (MIXER_ATTN, MIXER_MLA, MIXER_CROSS)


def _mixer_scope(mixer: str):
    """``attention`` for the attention mixers; recurrent mixers (Mamba)
    stay unscoped."""
    return (jax.named_scope("attention") if mixer in _ATTENTION_MIXERS
            else contextlib.nullcontext())


def apply_block(cfg: ModelConfig, kind: LayerKind, params: dict, x: jax.Array,
                ctx: BlockCtx):
    """Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), f32)
    cache = ctx.cache or {}

    if kind.mixer == MIXER_RWKV:
        x, mc, a = S.apply_rwkv(cfg, params["mixer"], x,
                                cache=cache.get("mixer"), tp_axis=ctx.tp_axis,
                                ln1=params["ln1"], ln2=params["ln2"])
        mc = L.cast_like(mc, cache.get("mixer"))
        return x, ({"mixer": mc} if mc is not None else None), aux + a

    new_cache: dict = {}
    # named scopes (op_name metadata of every op, no change to the math)
    # let a profile charge device time to the attention and the MLP
    with _mixer_scope(kind.mixer):
        h = L.rms_norm(params["ln1"], x, cfg.rms_eps)
        if kind.mixer == MIXER_ATTN:
            y, mc, a = L.apply_attention(
                cfg, params["mixer"], h, pos0=ctx.pos0,
                cache=cache.get("mixer"), is_global=ctx.is_global,
                causal=ctx.causal, tp_axis=ctx.tp_axis, kv_block=ctx.kv_block,
                sp_axis=ctx.sp_axis if ctx.is_global else None,
                block_table=ctx.block_table, paged_kernel=ctx.paged_kernel,
                kv_extent=ctx.kv_extent)
        elif kind.mixer == MIXER_MLA:
            y, mc, a = L.apply_mla(
                cfg, params["mixer"], h, pos0=ctx.pos0,
                cache=cache.get("mixer"), tp_axis=ctx.tp_axis,
                kv_block=ctx.kv_block)
        elif kind.mixer == MIXER_CROSS:
            y, mc, a = L.apply_cross_attention(
                cfg, params["mixer"], h, memory=ctx.memory,
                cache=cache.get("mixer"), tp_axis=ctx.tp_axis)
        elif kind.mixer == MIXER_MAMBA:
            y, mc, a = S.apply_mamba(cfg, params["mixer"], h,
                                     cache=cache.get("mixer"),
                                     tp_axis=ctx.tp_axis)
        else:
            raise ValueError(kind.mixer)
        x = x + y
        aux += a
        if mc is not None:
            new_cache["mixer"] = L.cast_like(mc, cache.get("mixer"))

    if kind.extra_cross:
        with jax.named_scope("attention"):
            h = L.rms_norm(params["ln_cross"], x, cfg.rms_eps)
            y, cc, _ = L.apply_cross_attention(
                cfg, params["cross"], h, memory=ctx.memory,
                cache=cache.get("cross"), tp_axis=ctx.tp_axis)
            x = x + y
            if cc is not None:
                new_cache["cross"] = L.cast_like(cc, cache.get("cross"))

    with jax.named_scope("mlp"):
        h = L.rms_norm(params["ln2"], x, cfg.rms_eps)
        if kind.mlp == MLP_MOE:
            y, _, a = L.apply_moe(cfg, params["mlp"], h, tp_axis=ctx.tp_axis)
        else:
            y, _, a = L.apply_mlp(cfg, params["mlp"], h, tp_axis=ctx.tp_axis)
        x = x + y
        aux += a
    return x, (new_cache or None), aux


# ---------------------------------------------------------------------------
# Stacked-layer scan support (fused decode hot path)
# ---------------------------------------------------------------------------

def stack_blocks(blocks: list) -> dict:
    """Stack per-layer block param trees along a new leading layer dim.

    All blocks must share one pytree structure (same ``LayerKind``); the
    result is scannable with ``jax.lax.scan`` (maxtext stacked-pytree idiom).
    """
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


def scan_runs(cfg: ModelConfig, lo: int, hi: int) -> list[tuple[int, int]]:
    """Partition layers [lo, hi) into maximal scannable runs.

    A run groups consecutive layers whose block params and caches stack:
    identical ``LayerKind`` (param/cache pytree structure) and identical
    global/local attention flavor (cache seq length + masking).  Homogeneous
    models collapse to one run per stage; hybrid patterns (e.g. jamba,
    gemma3's 5:1 local:global) fall back to shorter runs, with single-layer
    runs executed unrolled.
    """
    runs: list[tuple[int, int]] = []
    start = lo
    prev = None
    for li in range(lo, hi):
        sig = (cfg.layer_kind(li), cfg.is_global_layer(li))
        if prev is not None and sig != prev:
            runs.append((start, li))
            start = li
        prev = sig
    if hi > lo:
        runs.append((start, hi))
    return runs


# ---------------------------------------------------------------------------
# Whole-model params
# ---------------------------------------------------------------------------

def init_model(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    """Full (unstacked) param tree: embed, blocks list, final norm, head."""
    n_extra = cfg.encoder_layers
    keys = jax.random.split(key, cfg.n_layers + n_extra + 3)
    p: dict = {
        "embed": jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model),
                                   dtype) * (1.0 / math.sqrt(cfg.d_model)),
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
        "blocks": [init_block(keys[2 + i], cfg, cfg.layer_kind(i), dtype)
                   for i in range(cfg.n_layers)],
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.random.normal(
            keys[1], (cfg.d_model, cfg.vocab_size), dtype) * (1.0 / math.sqrt(cfg.d_model))
    if cfg.encoder_layers:
        enc_kind = LayerKind(mixer=MIXER_ATTN, mlp=MLP_DENSE)
        p["encoder"] = {
            "blocks": [init_block(keys[2 + cfg.n_layers + i], cfg, enc_kind, dtype)
                       for i in range(cfg.encoder_layers)],
            "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
        }
    if cfg.rope_theta == 0:            # learned positions (whisper)
        max_pos = 65_536
        p["pos_embed"] = jax.random.normal(
            keys[-1], (max_pos, cfg.d_model), dtype) * 0.02
    return p


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    """Exact parameter count via eval_shape (no allocation)."""
    shapes = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    total = sum(math.prod(x.shape) if x.shape else 1
                for x in jax.tree.leaves(shapes))
    if active_only and cfg.moe is not None:
        n_moe_layers = sum(1 for i in range(cfg.n_layers)
                           if cfg.layer_kind(i).mlp == MLP_MOE)
        per_expert = 3 * cfg.d_model * cfg.moe.d_expert
        routed = n_moe_layers * cfg.moe.n_experts * per_expert
        active = n_moe_layers * cfg.moe.top_k * per_expert
        total = total - routed + active
    return total
