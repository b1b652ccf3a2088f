"""SPMD pipeline parallelism: GPipe microbatch rotation inside shard_map.

Layer parameters are stacked with leading (stage, patterns_per_stage) dims
and sharded over the "stage" mesh axis; microbatch activations rotate between
stages via ``jax.lax.ppermute``.  Tensor parallelism runs inside each stage
over the "tensor" axis; embed / lm_head are vocab-parallel over
("stage", "tensor").  This module builds the three step functions the
launcher and dry-run lower: ``train_step``, ``prefill_step``, ``decode_step``.

FlexPipe connection: ``PipelinePlan(stages, tensor, replica, microbatches)``
is the granularity the controller (repro.core) selects; a refactoring event
re-invokes these builders with a new plan and migrates state.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, PipelinePlan, ShapeConfig
from repro.models import layers as L
from repro.models.kvcache import layer_cache_struct
from repro.models.transformer import BlockCtx, apply_block, init_block
from repro.parallel.sharding import (
    DP_AXES, VP_AXES, apply_fsdp, fsdp_gather, refine_mesh,
    stacked_param_specs, shardings)
from repro.training.optimizer import AdamWConfig, OptState, adamw_update

f32 = jnp.float32

# ---------------------------------------------------------------------------
# Param stacking
# ---------------------------------------------------------------------------

def _tree_stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def stack_params(cfg: ModelConfig, plan: PipelinePlan, params: dict) -> dict:
    """Unstacked model params -> stage-stacked tree.

    Layer i = (s*pps + p)*ps + j lives at stages[str(j)][s, p].
    """
    S = plan.stages
    ps = cfg.pattern_size
    pps = cfg.n_patterns // S
    blocks = params["blocks"]
    stages = {}
    for j in range(ps):
        per_stage = [
            _tree_stack([blocks[(s * pps + p) * ps + j] for p in range(pps)])
            for s in range(S)]
        stages[str(j)] = _tree_stack(per_stage)
    out = {"embed": params["embed"], "final_norm": params["final_norm"],
           "stages": stages}
    for k in ("lm_head", "pos_embed"):
        if k in params:
            out[k] = params[k]
    if "encoder" in params:
        assert plan.stages == 1, "encoder-decoder supports S=1 only (DESIGN.md §5)"
        out["encoder"] = {
            "blocks": _tree_stack(params["encoder"]["blocks"]),
            "final_norm": params["encoder"]["final_norm"]}
    return out


def unstack_params(cfg: ModelConfig, plan: PipelinePlan, stacked: dict) -> dict:
    S, ps = plan.stages, cfg.pattern_size
    pps = cfg.n_patterns // S
    blocks = [None] * cfg.n_layers
    for j in range(ps):
        tree = stacked["stages"][str(j)]
        for s in range(S):
            for p in range(pps):
                blocks[(s * pps + p) * ps + j] = jax.tree.map(
                    lambda l: l[s, p], tree)
    out = {"embed": stacked["embed"], "final_norm": stacked["final_norm"],
           "blocks": blocks}
    for k in ("lm_head", "pos_embed"):
        if k in stacked:
            out[k] = stacked[k]
    if "encoder" in stacked:
        n_enc = cfg.encoder_layers
        out["encoder"] = {
            "blocks": [jax.tree.map(lambda l: l[i], stacked["encoder"]["blocks"])
                       for i in range(n_enc)],
            "final_norm": stacked["encoder"]["final_norm"]}
    return out


def stacked_param_struct(cfg: ModelConfig, plan: PipelinePlan,
                         dtype=jnp.bfloat16):
    """ShapeDtypeStruct tree of the stacked params (no allocation)."""
    from repro.models.transformer import init_model
    return jax.eval_shape(
        lambda: stack_params(cfg, plan,
                             init_model(jax.random.PRNGKey(0), cfg, dtype)))


# ---------------------------------------------------------------------------
# Vocab-parallel embed / head / cross-entropy
# ---------------------------------------------------------------------------

def _vp_rank(plan: PipelinePlan):
    return (jax.lax.axis_index("stage") * plan.tensor
            + jax.lax.axis_index("tensor"))


def vp_embed(cfg: ModelConfig, plan: PipelinePlan, stacked: dict,
             tokens: jax.Array, pos0=0) -> jax.Array:
    """tokens (B, S) -> (B, S, d); embed table sharded over VP_AXES."""
    emb = stacked["embed"]
    Vloc = emb.shape[0]
    lid = tokens - _vp_rank(plan) * Vloc
    valid = (lid >= 0) & (lid < Vloc)
    x = emb[jnp.clip(lid, 0, Vloc - 1)] * valid[..., None].astype(emb.dtype)
    x = jax.lax.psum(x, VP_AXES)
    if cfg.rope_theta == 0 and "pos_embed" in stacked:
        S = tokens.shape[1]
        x = x + stacked["pos_embed"][pos0 + jnp.arange(S)][None].astype(x.dtype)
    return x


def _vp_head_w(cfg: ModelConfig, stacked: dict):
    return stacked["embed"].T if cfg.tie_embeddings else stacked["lm_head"]


def vp_logits(cfg: ModelConfig, stacked: dict, x: jax.Array) -> jax.Array:
    """Final-norm + head on the local vocab slice. x (B,S,d) -> (B,S,Vloc)."""
    h = L.rms_norm(stacked["final_norm"], x, cfg.rms_eps)
    return jnp.einsum("bsd,dv->bsv", h, _vp_head_w(cfg, stacked))


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _pmax_sg(x, axes):
    """pmax with a zero gradient (numerical-stability shift in the CE)."""
    return jax.lax.pmax(x, axes)


def _pmax_sg_fwd(x, axes):
    return jax.lax.pmax(x, axes), None


def _pmax_sg_bwd(axes, _, g):
    return (jnp.zeros_like(g),)


_pmax_sg.defvjp(_pmax_sg_fwd, _pmax_sg_bwd)


def vp_cross_entropy(cfg: ModelConfig, plan: PipelinePlan, stacked: dict,
                     x: jax.Array, labels: jax.Array,
                     chunk: int = 512) -> tuple[jax.Array, jax.Array]:
    """Vocab-parallel CE, seq-chunked. Returns (sum_nll, token_count)."""
    B, S, d = x.shape
    Vloc = stacked["embed"].shape[0]
    rank = _vp_rank(plan)
    w = _vp_head_w(cfg, stacked)
    h = L.rms_norm(stacked["final_norm"], x, cfg.rms_eps)

    nchunk = max(S // max(min(chunk, S), 1), 1)
    csz = S // nchunk
    hc = h[:, :nchunk * csz].reshape(B, nchunk, csz, d).transpose(1, 0, 2, 3)
    lc = labels[:, :nchunk * csz].reshape(B, nchunk, csz).transpose(1, 0, 2)

    def body(acc, inp):
        hx, lb = inp
        logits = jnp.einsum("bsd,dv->bsv", hx, w).astype(f32)
        m = _pmax_sg(logits.max(-1), VP_AXES)
        se = jax.lax.psum(jnp.exp(logits - m[..., None]).sum(-1), VP_AXES)
        lse = m + jnp.log(se)
        lid = lb - rank * Vloc
        valid = (lid >= 0) & (lid < Vloc)
        ll = jnp.take_along_axis(
            logits, jnp.clip(lid, 0, Vloc - 1)[..., None], axis=-1)[..., 0]
        ll = jax.lax.psum(jnp.where(valid, ll, 0.0), VP_AXES)
        return acc + (lse - ll).sum(), None

    total, _ = jax.lax.scan(body, jnp.zeros((), f32), (hc, lc))
    return total, jnp.asarray(B * nchunk * csz, f32)


# ---------------------------------------------------------------------------
# Stage execution
# ---------------------------------------------------------------------------

def _stage_kinds(cfg: ModelConfig):
    return [cfg.layer_kind(j) for j in range(cfg.pattern_size)]


def run_stage(cfg: ModelConfig, plan: PipelinePlan, stage_params: dict,
              x: jax.Array, cache: Optional[dict], *, pos0, memory=None,
              causal=True, sp_axis=None, kv_block=1024, remat=False,
              fsdp_dims=None):
    """Apply one stage (= pps repeating patterns). stage_params/cache leaves
    have leading (pps,); returns (x, new_cache, aux_sum).

    fsdp_dims: per-leaf all-gather dims (sliced-leaf indexing) — params are
    gathered from their data-sharded storage just before use, inside the
    remat boundary so the backward pass re-gathers (ZeRO-3 semantics)."""
    kinds = _stage_kinds(cfg)
    tp = "tensor" if plan.tensor > 1 else None

    def pattern_body(carry, xs):
        x = carry
        params_p, cache_p = xs
        if fsdp_dims is not None:
            gd = jnp.float8_e4m3fn if plan.fsdp_fp8_gather else None
            params_p = fsdp_gather(params_p, fsdp_dims, gather_dtype=gd)
        aux = jnp.zeros((), f32)
        new_cache = {}
        for j, kind in enumerate(kinds):
            ctx = BlockCtx(pos0=pos0,
                           cache=cache_p[str(j)] if cache_p is not None else None,
                           memory=memory, is_global=cfg.is_global_layer(j),
                           causal=causal, tp_axis=tp, sp_axis=sp_axis,
                           kv_block=kv_block)
            x, nc, a = apply_block(cfg, kind, params_p[str(j)], x, ctx)
            aux += a
            new_cache[str(j)] = nc if nc is not None else {}
        return x, (new_cache, aux)

    body = jax.checkpoint(pattern_body) if remat else pattern_body
    xs = (stage_params, cache)
    if cache is None:
        # scan needs a pytree; use params only and synthesize empty caches
        def body2(c, p):
            return body(c, (p, None))
        wrapped = body2
        x, (caches, auxs) = jax.lax.scan(wrapped, x, stage_params)
    else:
        x, (caches, auxs) = jax.lax.scan(body, x, xs)
    return x, caches, auxs.sum()


def run_encoder_stacked(cfg: ModelConfig, plan: PipelinePlan, stacked: dict,
                        frames: jax.Array, kv_block=1024) -> jax.Array:
    """Whisper encoder (S=1): scan over stacked encoder blocks."""
    tp = "tensor" if plan.tensor > 1 else None
    x = frames
    if cfg.rope_theta == 0 and "pos_embed" in stacked:
        x = x + stacked["pos_embed"][: x.shape[1]][None].astype(x.dtype)
    kind = _stage_kinds(cfg)[0].__class__()     # default attn/dense kind

    def body(x, bp):
        ctx = BlockCtx(causal=False, tp_axis=tp, kv_block=kv_block)
        y, _, _ = apply_block(cfg, kind, bp, x, ctx)
        return y, None

    x, _ = jax.lax.scan(body, x, stacked["encoder"]["blocks"])
    return L.rms_norm(stacked["encoder"]["final_norm"], x, cfg.rms_eps)


# ---------------------------------------------------------------------------
# Pipelined sequence pass (train forward / prefill)
# ---------------------------------------------------------------------------

def _rotate(x, plan: PipelinePlan):
    if plan.stages == 1:
        return x
    perm = [(i, (i + 1) % plan.stages) for i in range(plan.stages)]
    return jax.tree.map(lambda l: jax.lax.ppermute(l, "stage", perm), x)


def _mb_slice(tree, mb, Bm):
    """Slice microbatch [mb*Bm, (mb+1)*Bm) on the batch dim (axis 1 after
    the leading pps dim) of every cache leaf."""
    return jax.tree.map(
        lambda l: jax.lax.dynamic_slice_in_dim(l, mb * Bm, Bm, axis=1), tree)


def _mb_update(tree, upd, mb, Bm, valid):
    def one(l, u):
        old = jax.lax.dynamic_slice_in_dim(l, mb * Bm, Bm, axis=1)
        u = jnp.where(valid, u.astype(l.dtype), old)
        return jax.lax.dynamic_update_slice_in_dim(l, u, mb * Bm, axis=1)
    return jax.tree.map(one, tree, upd)


def pipeline_seq_pass(cfg: ModelConfig, plan: PipelinePlan, stacked: dict,
                      tokens: jax.Array, *, labels=None, caches=None,
                      memory_all=None, frames_all=None, kv_block=1024,
                      remat=False, fsdp_ctx=None):
    """Pipelined pass over full sequences (train fwd or prefill).

    tokens (Bl, S) local batch; M = plan.microbatches must divide Bl.
    Returns dict with: loss_sum/token_count (if labels), last_logits
    (B, Vloc) (if caches is not None), new caches, aux.
    """
    stacked = fsdp_gather_top(stacked, fsdp_ctx)
    stage_dims = fsdp_ctx["stages"] if fsdp_ctx is not None else None
    Bl, Sq = tokens.shape
    M = plan.microbatches
    Bm = Bl // M
    S_st = plan.stages
    stage_idx = jax.lax.axis_index("stage")
    d = cfg.d_model
    dt = stacked["embed"].dtype

    toks = tokens.reshape(M, Bm, Sq)
    labs = labels.reshape(M, Bm, Sq) if labels is not None else None
    n_ticks = M + S_st - 1
    caches_loc = caches  # leaves (pps, B_all, ...) — stage dim pre-squeezed

    def tick(carry, t):
        state, caches_c, loss_sum, tok_count, aux_sum, last_logits = carry
        mb_in = jnp.clip(t, 0, M - 1)
        x_in = vp_embed(cfg, plan, stacked,
                        jax.lax.dynamic_index_in_dim(toks, mb_in, 0, False))
        # this device's CURRENT microbatch (for cache slicing / memory)
        mb_cur = jnp.clip(t - stage_idx, 0, M - 1)
        valid_cur = (t - stage_idx >= 0) & (t - stage_idx < M)
        state = jnp.where(stage_idx == 0, x_in.astype(dt), state)

        memory = None
        if memory_all is not None:
            memory = jax.lax.dynamic_index_in_dim(memory_all, mb_cur, 0, False)
        if frames_all is not None:
            fr = jax.lax.dynamic_index_in_dim(frames_all, mb_cur, 0, False)
            memory = run_encoder_stacked(cfg, plan, stacked, fr, kv_block)

        cache_mb = _mb_slice(caches_c, mb_cur, Bm) if caches_c is not None else None
        out, new_cache_mb, aux = run_stage(
            cfg, plan, _squeeze_stage(stacked["stages"]), state, cache_mb,
            pos0=0, memory=memory, causal=True, kv_block=kv_block, remat=False,
            fsdp_dims=stage_dims)
        aux_sum = aux_sum + jnp.where(valid_cur, aux, 0.0)
        if caches_c is not None:
            caches_c = _mb_update(caches_c, new_cache_mb, mb_cur, Bm, valid_cur)

        # emission from last stage
        mb_out = jnp.clip(t - (S_st - 1), 0, M - 1)
        emit = (t >= S_st - 1) & (t - (S_st - 1) < M)
        out_b = jax.lax.psum(
            jnp.where(stage_idx == S_st - 1, out, jnp.zeros_like(out)), "stage") \
            if S_st > 1 else out
        if labs is not None:
            lb = jax.lax.dynamic_index_in_dim(labs, mb_out, 0, False)
            nll, cnt = vp_cross_entropy(cfg, plan, stacked, out_b, lb)
            loss_sum = loss_sum + jnp.where(emit, nll, 0.0)
            tok_count = tok_count + jnp.where(emit, cnt, 0.0)
        if last_logits is not None:
            lg = vp_logits(cfg, stacked, out_b[:, -1:, :])[:, 0, :]
            last_logits = jax.lax.dynamic_update_slice_in_dim(
                last_logits,
                jnp.where(emit, lg, jax.lax.dynamic_slice_in_dim(
                    last_logits, mb_out * Bm, Bm, axis=0)),
                mb_out * Bm, axis=0)

        state = _rotate(out, plan)
        return (state, caches_c, loss_sum, tok_count, aux_sum, last_logits), None

    Vloc = stacked["embed"].shape[0]
    init = (jnp.zeros((Bm, Sq, d), dt), caches_loc, jnp.zeros((), f32),
            jnp.zeros((), f32), jnp.zeros((), f32),
            jnp.zeros((Bl, Vloc), f32) if caches is not None else None)
    # remat at TICK granularity: the backward pass recomputes the whole tick
    # from the (small) carried state instead of saving per-layer residuals —
    # cuts activation memory from O(ticks·layers·acts) to O(ticks·state)
    tick_fn = jax.checkpoint(tick) if remat else tick
    (state, caches_out, loss_sum, tok_count, aux_sum, last_logits), _ = \
        jax.lax.scan(tick_fn, init, jnp.arange(n_ticks))
    return {"loss_sum": loss_sum, "token_count": tok_count,
            "aux": aux_sum, "caches": caches_out, "last_logits": last_logits}


def _squeeze_stage(stages_tree):
    """Local stage-axis (size 1 per shard) -> squeezed leading dim."""
    return jax.tree.map(lambda l: l[0], stages_tree)


# ---------------------------------------------------------------------------
# FSDP plumbing
# ---------------------------------------------------------------------------

def fsdp_transform(plan: PipelinePlan, pstruct: dict, pspecs: dict,
                   data_size: int):
    """Split the fsdp spec rewrite between stage-stacked leaves (min_dim=2:
    never the (S, pps) dims) and top-level leaves.

    Returns (new_pspecs, fsdp_ctx) where fsdp_ctx = {"top": dims-tree over
    non-stage entries, "stages": dims adjusted to sliced-leaf indexing}.
    """
    if not plan.fsdp:
        return pspecs, None
    new_specs = dict(pspecs)
    st_specs, st_dims = apply_fsdp(pspecs["stages"], pstruct["stages"],
                                   data_size, min_dim=2)
    new_specs["stages"] = st_specs
    top_dims = {}
    for k in pstruct:
        if k == "stages":
            continue
        min_dim = 1 if k == "encoder" else 0
        sp, dims = apply_fsdp(pspecs[k], pstruct[k], data_size, min_dim)
        new_specs[k] = sp
        top_dims[k] = dims
    stage_dims = jax.tree.map(lambda d: d - 2 if d >= 2 else -1, st_dims)
    return new_specs, {"top": top_dims, "stages": stage_dims}


def fsdp_gather_top(stacked: dict, fsdp_ctx):
    """Gather non-stage params (embed/head/norms) once per step."""
    if fsdp_ctx is None:
        return stacked
    out = dict(stacked)
    for k, dims in fsdp_ctx["top"].items():
        out[k] = fsdp_gather(stacked[k], dims)
    return out


# ---------------------------------------------------------------------------
# Pipelined decode pass
# ---------------------------------------------------------------------------

def pipeline_decode_pass(cfg: ModelConfig, plan: PipelinePlan, stacked: dict,
                         tokens: jax.Array, caches, pos, *, kv_block=1024,
                         fsdp_ctx=None):
    """One token for every request. tokens (Bl, 1); caches leaves
    (pps, B_all, ...) local; pos: int32 scalar cache length.

    Returns (logits (Bl, Vloc), new caches).
    """
    stacked = fsdp_gather_top(stacked, fsdp_ctx)
    stage_dims = fsdp_ctx["stages"] if fsdp_ctx is not None else None
    Bl = tokens.shape[0]
    M = plan.microbatches
    Bm = Bl // M
    S_st = plan.stages
    stage_idx = jax.lax.axis_index("stage")
    d = cfg.d_model
    dt = stacked["embed"].dtype
    sp_axis = "data" if plan.seq_parallel_kv else None

    toks = tokens.reshape(M, Bm, 1)
    n_ticks = M + S_st - 1
    Vloc = stacked["embed"].shape[0]

    def tick(carry, t):
        state, caches_c, logits = carry
        mb_in = jnp.clip(t, 0, M - 1)
        x_in = vp_embed(cfg, plan, stacked,
                        jax.lax.dynamic_index_in_dim(toks, mb_in, 0, False),
                        pos0=pos)
        state = jnp.where(stage_idx == 0, x_in.astype(dt), state)
        mb_cur = jnp.clip(t - stage_idx, 0, M - 1)
        valid_cur = (t - stage_idx >= 0) & (t - stage_idx < M)

        cache_mb = _mb_slice(caches_c, mb_cur, Bm)
        out, new_cache_mb, _ = run_stage(
            cfg, plan, _squeeze_stage(stacked["stages"]), state, cache_mb,
            pos0=pos, causal=True, sp_axis=sp_axis, kv_block=kv_block,
            fsdp_dims=stage_dims)
        caches_c = _mb_update(caches_c, new_cache_mb, mb_cur, Bm, valid_cur)

        mb_out = jnp.clip(t - (S_st - 1), 0, M - 1)
        emit = (t >= S_st - 1) & (t - (S_st - 1) < M)
        out_b = jax.lax.psum(
            jnp.where(stage_idx == S_st - 1, out, jnp.zeros_like(out)), "stage") \
            if S_st > 1 else out
        lg = vp_logits(cfg, stacked, out_b)[:, 0, :]
        old = jax.lax.dynamic_slice_in_dim(logits, mb_out * Bm, Bm, axis=0)
        logits = jax.lax.dynamic_update_slice_in_dim(
            logits, jnp.where(emit, lg, old), mb_out * Bm, axis=0)

        state = _rotate(out, plan)
        return (state, caches_c, logits), None

    init = (jnp.zeros((Bm, 1, d), dt), caches, jnp.zeros((Bl, Vloc), f32))
    (_, caches_out, logits), _ = jax.lax.scan(tick, init, jnp.arange(n_ticks))
    return logits, caches_out


# ---------------------------------------------------------------------------
# Stacked cache structs & specs
# ---------------------------------------------------------------------------

def stacked_cache_struct(cfg: ModelConfig, plan: PipelinePlan,
                         shape: ShapeConfig, dtype=jnp.bfloat16):
    """Global ShapeDtypeStruct tree: {j: cache leaves (S, pps, B, ...)}."""
    S = plan.stages
    pps = cfg.n_patterns // S
    B = shape.global_batch
    out = {}
    for j in range(cfg.pattern_size):
        per_layer = layer_cache_struct(cfg, j, B, shape.seq_len, dtype,
                                       tensor_shards=1)
        out[str(j)] = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((S, pps) + l.shape, l.dtype),
            per_layer, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return out


def stacked_cache_specs(cfg: ModelConfig, plan: PipelinePlan,
                        shape: ShapeConfig, cache_tree):
    """PartitionSpecs congruent with stacked_cache_struct."""
    sp = plan.seq_parallel_kv
    T = plan.tensor

    def spec_for(path, leaf):
        names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        j = int(names[0])
        name = names[-1]
        nd = len(leaf.shape)
        dims: list = [None] * nd
        dims[0] = "stage"
        dims[2] = _dp_entry(shape, plan)
        if name in ("k", "v"):
            is_window = (cfg.sliding_window and not cfg.is_global_layer(j)
                         and "cross" not in names)
            if T > 1 and leaf.shape[3] % T == 0:
                dims[3] = "tensor"
            if sp and not is_window and "cross" not in names:
                dims[4] = "data"
        elif name in ("latent", "k_rope"):
            if sp:
                dims[3] = "data"
        elif name == "ssm":
            if T > 1 and leaf.shape[3] % T == 0:
                dims[3] = "tensor"
        elif name == "conv":
            if T > 1 and leaf.shape[4] % T == 0:
                dims[4] = "tensor"
        elif name == "wkv":
            if T > 1 and leaf.shape[3] % T == 0:
                dims[3] = "tensor"
        # sx_tm / sx_cm: replicated beyond batch/stage
        return P(*dims)

    return jax.tree_util.tree_map_with_path(
        spec_for, cache_tree,
        is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct, jax.Array)))


# ---------------------------------------------------------------------------
# Gradient synchronization
# ---------------------------------------------------------------------------

ALL_AXES = ("pod", "data", "stage", "tensor", "replica")


def _spec_axes(spec: P) -> set:
    out = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            out.update(entry)
        else:
            out.add(entry)
    return out


def grad_sync(grads, pspecs, mesh: Mesh, compress_pod: bool = False):
    """psum each grad leaf over every mesh axis it is replicated on.

    With ``compress_pod``, the cross-pod (DCN) reduction uses int8
    quantization (training/compression.py) — the paper-beyond trick for
    multi-pod training.
    """
    from repro.training.compression import compressed_psum
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def sync(g, spec):
        missing = tuple(a for a in ALL_AXES
                        if a not in _spec_axes(spec) and sizes.get(a, 1) > 1)
        if not missing:
            return g
        if compress_pod and "pod" in missing:
            rest = tuple(a for a in missing if a != "pod")
            if rest:
                g = jax.lax.psum(g, rest)
            return compressed_psum(g, "pod")
        return jax.lax.psum(g, missing)

    return jax.tree.map(sync, grads, pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def grad_norm_sq(grads, pspecs, mesh: Mesh):
    """Exact global ||g||² for sharded/replicated mixed trees."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    total = jnp.zeros((), f32)
    for g, spec in zip(jax.tree.leaves(grads),
                       jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P))):
        rep = 1
        for a in ("stage", "tensor", "data"):
            if a not in _spec_axes(spec):
                rep *= sizes.get(a, 1)
        total = total + jnp.sum(jnp.square(g.astype(f32))) / rep
    return jax.lax.psum(total, ("stage", "tensor", "data"))


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------

def _cache_squeeze(tree):
    return jax.tree.map(lambda l: l[0], tree)


def _cache_unsqueeze(tree):
    return jax.tree.map(lambda l: l[None], tree)


def _dp_entry(shape: ShapeConfig, plan: PipelinePlan):
    """Batch-dim sharding: DP_AXES when the global batch divides the
    worst-case (multi-pod) dp degree, else replicated (e.g. batch-1 decode)."""
    if plan.seq_parallel_kv or shape.global_batch % (32 * plan.replica) != 0:
        return None
    return DP_AXES


def _batch_in_specs(cfg: ModelConfig, shape: ShapeConfig, plan: PipelinePlan):
    """Input specs for the batch dict given arch extras."""
    dp = _dp_entry(shape, plan)
    specs = {"tokens": P(dp, None)}
    if shape.kind == "train":
        specs["labels"] = P(dp, None)
    if cfg.encoder_layers and shape.kind != "decode":
        specs["frames"] = P(dp, None, None)
    if cfg.n_memory_tokens and not cfg.encoder_layers and shape.kind != "decode":
        specs["memory"] = P(dp, None, None)
    return specs


def batch_struct(cfg: ModelConfig, shape: ShapeConfig, plan: PipelinePlan,
                 dtype=jnp.bfloat16):
    """Global ShapeDtypeStructs for the step inputs."""
    B = shape.global_batch
    Sq = 1 if shape.is_decode else shape.seq_len
    out = {"tokens": jax.ShapeDtypeStruct((B, Sq), jnp.int32)}
    if shape.kind == "train":
        out["labels"] = jax.ShapeDtypeStruct((B, Sq), jnp.int32)
    if cfg.encoder_layers and shape.kind != "decode":
        out["frames"] = jax.ShapeDtypeStruct((B, shape.seq_len, cfg.d_model), dtype)
    if cfg.n_memory_tokens and not cfg.encoder_layers and shape.kind != "decode":
        out["memory"] = jax.ShapeDtypeStruct((B, cfg.n_memory_tokens, cfg.d_model), dtype)
    return out


def build_train_step(cfg: ModelConfig, plan: PipelinePlan, base_mesh: Mesh,
                     shape: ShapeConfig, opt_cfg: AdamWConfig = AdamWConfig(),
                     param_dtype=jnp.bfloat16, compress_pod: bool = False,
                     aux_weight: float = 0.01):
    """Returns (jitted step, structs dict) — step(params, opt, batch)."""
    mesh = refine_mesh(base_mesh, plan)
    pstruct = stacked_param_struct(cfg, plan, param_dtype)
    pspecs = stacked_param_specs(cfg, plan, pstruct)
    data_size = dict(zip(mesh.axis_names, mesh.devices.shape))["data"]
    pspecs, fsdp_ctx = fsdp_transform(plan, pstruct, pspecs, data_size)
    ostruct = OptState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        m=jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, f32), pstruct),
        v=jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, f32), pstruct))
    ospecs = OptState(step=P(), m=pspecs, v=pspecs)
    bspecs = _batch_in_specs(cfg, shape, plan)
    bstruct = batch_struct(cfg, shape, plan, param_dtype)
    M = plan.microbatches

    def step(params, opt_state, batch):
        def loss_of(p):
            tokens = batch["tokens"]
            Bl = tokens.shape[0]
            Bm = Bl // M
            frames_all = memory_all = None
            if "frames" in batch:
                f = batch["frames"]
                frames_all = f.reshape(M, Bm, *f.shape[1:])
            if "memory" in batch:
                m = batch["memory"]
                memory_all = m.reshape(M, Bm, *m.shape[1:])
            res = pipeline_seq_pass(
                cfg, plan, p, tokens, labels=batch["labels"],
                frames_all=frames_all, memory_all=memory_all,
                remat=plan.remat, fsdp_ctx=fsdp_ctx)
            loss = (jax.lax.psum(res["loss_sum"], DP_AXES)
                    / jnp.maximum(jax.lax.psum(res["token_count"], DP_AXES), 1.0))
            aux = jax.lax.psum(res["aux"], ("stage",)) / max(M * cfg.n_layers, 1)
            return loss + aux_weight * aux, (loss, aux)

        (total, (loss, aux)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
        grads = grad_sync(grads, pspecs, mesh, compress_pod)
        nsq = grad_norm_sq(grads, pspecs, mesh)
        new_p, new_o, om = adamw_update(opt_cfg, params, grads, opt_state,
                                        extra_norm_sq=nsq)
        metrics = {"loss": loss, "aux": aux, **om}
        return new_p, new_o, metrics

    mspecs = {"loss": P(), "aux": P(), "grad_norm": P(), "lr": P()}
    fn = jax.shard_map(step, mesh=mesh,
                       in_specs=(pspecs, ospecs, bspecs),
                       out_specs=(pspecs, ospecs, mspecs), check_vma=False)
    jitted = jax.jit(
        fn,
        in_shardings=(shardings(mesh, pspecs), shardings(mesh, ospecs),
                      shardings(mesh, bspecs)),
        out_shardings=(shardings(mesh, pspecs), shardings(mesh, ospecs),
                       shardings(mesh, mspecs)),
        donate_argnums=(0, 1))
    structs = {"params": pstruct, "opt": ostruct, "batch": bstruct,
               "pspecs": pspecs, "mesh": mesh}
    return jitted, structs


def build_prefill_step(cfg: ModelConfig, plan: PipelinePlan, base_mesh: Mesh,
                       shape: ShapeConfig, param_dtype=jnp.bfloat16,
                       cache_dtype=None):
    cache_dtype = cache_dtype or (jnp.float8_e4m3fn if plan.kv_dtype == "fp8"
                                  else jnp.bfloat16)
    """step(params, batch) -> (last_logits (B, Vloc), caches)."""
    mesh = refine_mesh(base_mesh, plan)
    pstruct = stacked_param_struct(cfg, plan, param_dtype)
    pspecs = stacked_param_specs(cfg, plan, pstruct)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    pspecs, fsdp_ctx = fsdp_transform(plan, pstruct, pspecs, sizes["data"])
    cstruct = stacked_cache_struct(cfg, plan, shape, cache_dtype)
    cspecs = stacked_cache_specs(cfg, plan, shape, cstruct)
    bspecs = _batch_in_specs(cfg, shape, plan)
    bstruct = batch_struct(cfg, shape, plan, param_dtype)
    M = plan.microbatches

    def local_shape(leaf, spec):
        shp = list(leaf.shape)
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, (tuple, list)) else (entry,)
            for a in axes:
                shp[i] //= sizes.get(a, 1)
        return tuple(shp)

    def step(params, batch):
        tokens = batch["tokens"]
        Bl = tokens.shape[0]
        Bm = Bl // M
        frames_all = memory_all = None
        if "frames" in batch:
            f = batch["frames"]
            frames_all = f.reshape(M, Bm, *f.shape[1:])
        if "memory" in batch:
            m = batch["memory"]
            memory_all = m.reshape(M, Bm, *m.shape[1:])
        caches = jax.tree.map(
            lambda l, s: jnp.zeros(local_shape(l, s)[1:], l.dtype),
            cstruct, cspecs,
            is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct, P)))
        res = pipeline_seq_pass(cfg, plan, params, tokens, caches=caches,
                                frames_all=frames_all, memory_all=memory_all,
                                fsdp_ctx=fsdp_ctx)
        return res["last_logits"], _cache_unsqueeze(res["caches"])

    lspec = P(_dp_entry(shape, plan), VP_AXES)
    fn = jax.shard_map(step, mesh=mesh, in_specs=(pspecs, bspecs),
                       out_specs=(lspec, cspecs), check_vma=False)
    jitted = jax.jit(
        fn,
        in_shardings=(shardings(mesh, pspecs), shardings(mesh, bspecs)),
        out_shardings=(NamedSharding(mesh, lspec), shardings(mesh, cspecs)))
    structs = {"params": pstruct, "batch": bstruct, "cache": cstruct,
               "pspecs": pspecs, "cspecs": cspecs, "mesh": mesh}
    return jitted, structs


def build_decode_step(cfg: ModelConfig, plan: PipelinePlan, base_mesh: Mesh,
                      shape: ShapeConfig, param_dtype=jnp.bfloat16,
                      cache_dtype=None):
    cache_dtype = cache_dtype or (jnp.float8_e4m3fn if plan.kv_dtype == "fp8"
                                  else jnp.bfloat16)
    """step(params, caches, tokens, pos) -> (logits (B, Vloc), caches)."""
    mesh = refine_mesh(base_mesh, plan)
    pstruct = stacked_param_struct(cfg, plan, param_dtype)
    pspecs = stacked_param_specs(cfg, plan, pstruct)
    data_size = dict(zip(mesh.axis_names, mesh.devices.shape))["data"]
    pspecs, fsdp_ctx = fsdp_transform(plan, pstruct, pspecs, data_size)
    cstruct = stacked_cache_struct(cfg, plan, shape, cache_dtype)
    cspecs = stacked_cache_specs(cfg, plan, shape, cstruct)
    dp = _dp_entry(shape, plan)
    tok_spec = P(dp, None)
    lspec = P(dp, VP_AXES)

    def step(params, caches, tokens, pos):
        logits, new_caches = pipeline_decode_pass(
            cfg, plan, params, tokens, _cache_squeeze(caches), pos,
            fsdp_ctx=fsdp_ctx)
        return logits, _cache_unsqueeze(new_caches)

    fn = jax.shard_map(step, mesh=mesh,
                       in_specs=(pspecs, cspecs, tok_spec, P()),
                       out_specs=(lspec, cspecs), check_vma=False)
    jitted = jax.jit(
        fn,
        in_shardings=(shardings(mesh, pspecs), shardings(mesh, cspecs),
                      NamedSharding(mesh, tok_spec), NamedSharding(mesh, P())),
        out_shardings=(NamedSharding(mesh, lspec), shardings(mesh, cspecs)),
        donate_argnums=(1,))
    structs = {"params": pstruct, "cache": cstruct,
               "tokens": jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32),
               "pos": jax.ShapeDtypeStruct((), jnp.int32),
               "pspecs": pspecs, "cspecs": cspecs, "mesh": mesh}
    return jitted, structs
