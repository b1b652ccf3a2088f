"""Model-level entry points: forward, loss, prefill, decode (single-program).

These are the *semantic reference* implementations: no pipeline, no mesh.
``parallel/pipeline.py`` builds the distributed versions from the same blocks
and is tested for equivalence against these.

Batch dict convention:
  tokens:  (B, S) int32            — decoder/LM tokens
  frames:  (B, S_enc, d) float     — whisper encoder input (frontend stub)
  memory:  (B, M, d) float         — VLM image tokens (frontend stub)
  labels:  (B, S) int32            — training targets
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerKind, MIXER_ATTN, ModelConfig
from repro.models import layers as L
from repro.models.transformer import BlockCtx, apply_block
from repro.models.kvcache import init_cache

f32 = jnp.float32


@jax.named_scope("embed")
def embed_tokens(cfg: ModelConfig, params: dict, tokens: jax.Array,
                 pos0=0) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.rope_theta == 0 and "pos_embed" in params:
        S = tokens.shape[1]
        p0 = jnp.asarray(pos0)
        pos = (p0[:, None] if p0.ndim == 1 else p0) + jnp.arange(S)
        pe = params["pos_embed"][pos]            # (S, d) or (B, S, d) ragged
        x = x + (pe[None, :, :] if pe.ndim == 2 else pe)
    return x


@jax.named_scope("head")
def lm_head(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    h = L.rms_norm(params["final_norm"], x, cfg.rms_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("bsd,dv->bsv", h, w)


def run_encoder(cfg: ModelConfig, params: dict, frames: jax.Array,
                tp_axis=None) -> jax.Array:
    """Whisper encoder over precomputed frame embeddings (conv stub)."""
    x = frames
    if cfg.rope_theta == 0 and "pos_embed" in params:
        x = x + params["pos_embed"][: x.shape[1]][None, :, :]
    ctx = BlockCtx(causal=False, tp_axis=tp_axis)
    kind = LayerKind(mixer=MIXER_ATTN)
    for bp in params["encoder"]["blocks"]:
        x, _, _ = apply_block(cfg, kind, bp, x, ctx)
    return L.rms_norm(params["encoder"]["final_norm"], x, cfg.rms_eps)


def _decoder_memory(cfg: ModelConfig, params: dict, batch: dict, tp_axis):
    if cfg.encoder_layers and "frames" in batch:
        return run_encoder(cfg, params, batch["frames"], tp_axis)
    return batch.get("memory")


def forward(cfg: ModelConfig, params: dict, batch: dict, *,
            cache: Optional[list] = None, pos0=0, tp_axis=None,
            kv_block: int = 1024):
    """Run all decoder blocks. Returns (logits, new_cache, aux)."""
    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens, pos0)
    memory = _decoder_memory(cfg, params, batch, tp_axis)
    aux = jnp.zeros((), f32)
    new_cache = [] if cache is not None else None
    for i, bp in enumerate(params["blocks"]):
        ctx = BlockCtx(pos0=pos0, cache=cache[i] if cache is not None else None,
                       memory=memory, is_global=cfg.is_global_layer(i),
                       causal=True, tp_axis=tp_axis, kv_block=kv_block)
        x, nc, a = apply_block(cfg, cfg.layer_kind(i), bp, x, ctx)
        aux += a
        if new_cache is not None:
            new_cache.append(nc)
    logits = lm_head(cfg, params, x)
    return logits, new_cache, aux


def loss_fn(cfg: ModelConfig, params: dict, batch: dict,
            aux_weight: float = 0.01, tp_axis=None):
    """Next-token cross entropy (+ MoE aux). Returns (loss, metrics)."""
    logits, _, aux = forward(cfg, params, batch, tp_axis=tp_axis)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(f32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("mask", jnp.ones_like(labels, f32))
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    total = loss + aux_weight * aux
    return total, {"nll": loss, "aux": aux}


def prefill(cfg: ModelConfig, params: dict, batch: dict, max_seq: int,
            cache_dtype=jnp.bfloat16, tp_axis=None, kv_block: int = 1024):
    """Process the prompt, build the cache. Returns (last_logits, cache)."""
    B = batch["tokens"].shape[0]
    cache = init_cache(cfg, B, max_seq, cache_dtype)
    logits, cache, _ = forward(cfg, params, batch, cache=cache, pos0=0,
                               tp_axis=tp_axis, kv_block=kv_block)
    return logits[:, -1, :], cache


def decode_step(cfg: ModelConfig, params: dict, token: jax.Array, cache: list,
                pos: jax.Array, memory=None, tp_axis=None):
    """One decode step. token: (B, 1) int32; pos: int32 scalar (cache len).

    Returns (logits (B, vocab), new_cache).
    """
    batch = {"tokens": token}
    if memory is not None:
        batch["memory"] = memory
    logits, cache, _ = forward(cfg, params, batch, cache=cache, pos0=pos,
                               tp_axis=tp_axis)
    return logits[:, -1, :], cache


def greedy_generate(cfg: ModelConfig, params: dict, batch: dict, steps: int,
                    max_seq: int, tp_axis=None):
    """Reference autoregressive loop (tests / quickstart)."""
    last, cache = prefill(cfg, params, batch, max_seq, tp_axis=tp_axis)
    pos = batch["tokens"].shape[1]
    memory = batch.get("memory")
    toks = []
    tok = jnp.argmax(last, axis=-1)[:, None]
    for _ in range(steps):
        toks.append(tok)
        logits, cache = decode_step(cfg, params, tok, cache, pos, memory, tp_axis)
        tok = jnp.argmax(logits, axis=-1)[:, None]
        pos = pos + 1
    return jnp.concatenate(toks, axis=1), cache
