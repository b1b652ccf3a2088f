"""Precompiled executor cache: the engine's jitted hot-path programs.

FlexPipe's inflight refactoring is only pause-free if changing stage
boundaries never re-traces XLA programs on the critical path (PipeBoost's
lesson: reconfiguration speed is compile-cache speed).  This module owns
every jitted program the engine dispatches, keyed so that refactoring
between already-seen granularities is a dictionary lookup:

* ``stage_prefill(lo, hi, ...)`` / ``stage_decode(lo, hi)`` — per
  layer-range programs, shared between any two pipeline configurations
  that cut the model at the same points.  Prefill writes the prompt's
  cache rows *directly into the batch slot* via
  ``jax.lax.dynamic_update_slice`` on donated full caches (no host-side
  temp-cache scatter), and the last stage ends with lm_head + argmax so
  only the first sampled token id crosses to host.
* ``fused_decode(boundaries)`` — one program per stage configuration:
  embed -> every stage (each stage's layer loop is a ``lax.scan`` over
  stacked per-stage block params, maxtext-style) -> lm_head -> on-device
  argmax.  Only the B sampled token ids (int32) return to host per tick.

Donation invariants
-------------------
Every program donates its KV-cache argument (``donate_argnums``): the
caller must treat the cache buffers it passed in as *consumed* and adopt
the returned ones.  Params, activations and token ids are never donated.

Program sharing
---------------
Jitted callables live in a process-wide table keyed by ``(ModelConfig,
program kind, ...)`` — configs are frozen/hashable and params are passed
as arguments, so engines serving the same architecture share compiled
executables.  Per-engine state (stacked run params, head params, hit/miss
stats) lives in ``ExecutorCache`` instances.  ``trace_count()`` is a
process-global counter bumped from inside every traced body; a warmed
``refactor()`` must leave it unchanged (regression-tested).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MIXER_ATTN, MIXER_CROSS, MIXER_MLA, ModelConfig
from repro.models.kvcache import init_cache
from repro.models.model import embed_tokens, lm_head
from repro.models.transformer import (BlockCtx, apply_block, scan_runs,
                                      stack_blocks)

# --------------------------------------------------------------------------
# Process-wide jitted-program table and trace counter
# --------------------------------------------------------------------------

_PROGRAMS: dict = {}
_TRACES = [0]                  # boxed so traced closures can bump it


def trace_count() -> int:
    """Total jit (re)traces across all executor programs in this process."""
    return _TRACES[0]


def _note_trace() -> None:
    # executes while jax is *tracing* a program body, i.e. once per retrace
    _TRACES[0] += 1


def _shared(key, builder):
    if key not in _PROGRAMS:
        _PROGRAMS[key] = builder()
    return _PROGRAMS[key]


def _slot_write(dst, src, slot):
    """Write a batch-1 cache leaf into row ``slot`` of the full-batch leaf
    (in place under donation)."""
    start = (slot,) + (0,) * (dst.ndim - 1)
    return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), start)


# --------------------------------------------------------------------------
# Program builders (pure: close over cfg/layout only, params come in as args)
# --------------------------------------------------------------------------

def _stage_ranges(cfg: ModelConfig, boundaries: tuple[int, ...]):
    return tuple(zip(boundaries, boundaries[1:] + (cfg.n_layers,)))


def _fused_decode_fn(cfg: ModelConfig, boundaries: tuple[int, ...],
                     scan_threshold: int, paged: bool = False,
                     paged_kernel: bool = False):
    """One decode tick for the whole pipeline configuration.

    Runs of at least ``scan_threshold`` identical layers execute as a
    ``lax.scan`` over stacked per-stage block params (bounds trace/compile
    time on deep stages — the cold-refactor lever); shorter runs unroll,
    which lets XLA update the donated per-layer caches fully in place
    instead of staging them through a stacked copy (the steady-state
    runtime lever; see BENCH_engine.json for the measured gap).

    Paged mode: caches are block POOLS and the tick takes the per-slot
    block tables as an extra (B, max_blocks) int32 argument — tables grow
    every tick but keep a fixed shape, so no retrace."""
    flat_runs = [r for lo, hi in _stage_ranges(cfg, boundaries)
                 for r in scan_runs(cfg, lo, hi)]

    def run_layers(extras, caches, run_params, tok, pos, bt):
        x = embed_tokens(cfg, extras, tok, pos0=pos)
        new = list(caches)
        for (lo, hi), rp in zip(flat_runs, run_params):
            kind = cfg.layer_kind(lo)
            glob = cfg.is_global_layer(lo)
            # length-1 runs always unroll (nothing to scan over; keeps the
            # routing consistent with _run_container for any threshold)
            if hi - lo == 1 or hi - lo < scan_threshold:
                for j, li in enumerate(range(lo, hi)):
                    bp = rp[li - lo] if isinstance(rp, list) else rp
                    ctx = BlockCtx(pos0=pos, cache=new[li], is_global=glob,
                                   block_table=bt, paged_kernel=paged_kernel)
                    x, nc, _ = apply_block(cfg, kind, bp, x, ctx)
                    new[li] = nc
            else:
                stk = stack_blocks([new[li] for li in range(lo, hi)])

                def body(x, inp, _kind=kind, _glob=glob):
                    bp, c = inp
                    ctx = BlockCtx(pos0=pos, cache=c, is_global=_glob,
                                   block_table=bt, paged_kernel=paged_kernel)
                    x, nc, _ = apply_block(cfg, _kind, bp, x, ctx)
                    return x, nc

                x, stk_new = jax.lax.scan(body, x, (rp, stk))
                for j, li in enumerate(range(lo, hi)):
                    new[li] = jax.tree.map(lambda l, _j=j: l[_j], stk_new)
        logits = lm_head(cfg, extras, x)
        with jax.named_scope("head"):        # the token pick is the head's
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return nxt, tuple(new)

    if paged:
        def tick(extras, caches, run_params, tok, pos, block_tables):
            _note_trace()
            return run_layers(extras, caches, run_params, tok, pos,
                              block_tables)
    else:
        def tick(extras, caches, run_params, tok, pos):
            _note_trace()
            return run_layers(extras, caches, run_params, tok, pos, None)

    return jax.jit(tick, donate_argnums=(1,))




def _stage_prefill_fn(cfg: ModelConfig, lo: int, hi: int, max_seq: int,
                      dtype, first: bool, last: bool, paged: bool = False):
    """Prompt pass over layers [lo, hi) writing rows straight into the slot.

    Paged mode replaces the slot index with the slot's (1, max_blocks)
    block-table row: the paged attention path scatters the prompt's KV
    straight through the table into the donated pools, so there is no
    batch-1 temp cache and no ``_slot_write`` pass."""

    if paged:
        def prefill(blocks, extras, inp, caches, block_row, true_len, memory):
            _note_trace()
            x = embed_tokens(cfg, extras, inp) if first else inp
            new = []
            for i, bp in enumerate(blocks):
                li = lo + i
                ctx = BlockCtx(pos0=0, cache=caches[i], memory=memory,
                               is_global=cfg.is_global_layer(li),
                               block_table=block_row)
                x, nc, _ = apply_block(cfg, cfg.layer_kind(li), bp, x, ctx)
                new.append(nc)
            if last:
                xl = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
                tok = jnp.argmax(lm_head(cfg, extras, xl)[:, -1, :], axis=-1)
                return tok.astype(jnp.int32), new
            return x, new

        return jax.jit(prefill, donate_argnums=(3,))

    def prefill(blocks, extras, inp, caches, slot, true_len, memory):
        _note_trace()
        x = embed_tokens(cfg, extras, inp) if first else inp
        tmp = init_cache(cfg, 1, max_seq, dtype, layers=range(lo, hi))
        fresh = []
        for i, bp in enumerate(blocks):
            li = lo + i
            ctx = BlockCtx(pos0=0, cache=tmp[i], memory=memory,
                           is_global=cfg.is_global_layer(li))
            x, nc, _ = apply_block(cfg, cfg.layer_kind(li), bp, x, ctx)
            fresh.append(nc)
        out = [jax.tree.map(lambda d, s: _slot_write(d, s, slot), dst, src)
               for dst, src in zip(caches, fresh)]
        if last:
            xl = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
            tok = jnp.argmax(lm_head(cfg, extras, xl)[:, -1, :], axis=-1)
            return tok.astype(jnp.int32), out
        return x, out

    return jax.jit(prefill, donate_argnums=(3,))


def _chunk_prefill_fn(cfg: ModelConfig, lo: int, hi: int, max_seq: int,
                      dtype, first: bool, last: bool, sample: bool,
                      chunk_len: int, kv_extent: int, paged: bool = False):
    """One prefill *chunk* over layers [lo, hi): ``chunk_len`` tokens are
    committed at a runtime offset ``pos0`` and attend over cache rows
    [0, ``kv_extent``) — all previously committed chunks plus this one.

    ``kv_extent`` is the whole prompt's pow2 bucket, so every chunk of a
    prompt reduces attention over the same extent a whole-prompt prefill
    would: greedy outputs stay bit-identical (unwritten rows past the
    prefix are causally masked and contribute exact zeros).  ``pos0`` is a
    traced scalar, so one program serves every chunk index of a given
    (chunk_len, kv_extent) shape.  ``sample`` adds lm_head + argmax on the
    row ``last_ix`` (the prompt's final token, chunk-relative) — set only
    on the final chunk's last stage.
    """

    if paged:
        def chunk(blocks, extras, inp, caches, block_row, pos0, last_ix,
                  memory):
            _note_trace()
            x = embed_tokens(cfg, extras, inp, pos0=pos0) if first else inp
            new = []
            for i, bp in enumerate(blocks):
                li = lo + i
                ctx = BlockCtx(pos0=pos0, cache=caches[i], memory=memory,
                               is_global=cfg.is_global_layer(li),
                               block_table=block_row, kv_extent=kv_extent)
                x, nc, _ = apply_block(cfg, cfg.layer_kind(li), bp, x, ctx)
                new.append(nc)
            if last and sample:
                xl = jax.lax.dynamic_slice_in_dim(x, last_ix, 1, axis=1)
                tok = jnp.argmax(lm_head(cfg, extras, xl)[:, -1, :], axis=-1)
                return tok.astype(jnp.int32), new
            return x, new

        return jax.jit(chunk, donate_argnums=(3,))

    def chunk(blocks, extras, inp, caches, slot, pos0, last_ix, memory):
        _note_trace()
        x = embed_tokens(cfg, extras, inp, pos0=pos0) if first else inp
        out = []
        for i, bp in enumerate(blocks):
            li = lo + i
            # batch-1 view of this slot's rows; the chunked attention path
            # reads committed rows [0, kv_extent) and writes [pos0, pos0+S)
            sub = jax.tree.map(
                lambda c: jax.lax.dynamic_slice(
                    c, (slot,) + (0,) * (c.ndim - 1), (1,) + c.shape[1:]),
                caches[i])
            ctx = BlockCtx(pos0=pos0, cache=sub, memory=memory,
                           is_global=cfg.is_global_layer(li),
                           kv_extent=kv_extent)
            x, nc, _ = apply_block(cfg, cfg.layer_kind(li), bp, x, ctx)
            out.append(jax.tree.map(lambda d, s: _slot_write(d, s, slot),
                                    caches[i], nc))
        if last and sample:
            xl = jax.lax.dynamic_slice_in_dim(x, last_ix, 1, axis=1)
            tok = jnp.argmax(lm_head(cfg, extras, xl)[:, -1, :], axis=-1)
            return tok.astype(jnp.int32), out
        return x, out

    return jax.jit(chunk, donate_argnums=(3,))


def _stage_decode_fn(cfg: ModelConfig, lo: int, hi: int):
    """Per-stage decode tick (the unfused fallback path)."""

    def decode(blocks, x, caches, pos, memory):
        _note_trace()
        new = []
        for i, bp in enumerate(blocks):
            li = lo + i
            ctx = BlockCtx(pos0=pos, cache=caches[i], memory=memory,
                           is_global=cfg.is_global_layer(li))
            x, nc, _ = apply_block(cfg, cfg.layer_kind(li), bp, x, ctx)
            new.append(nc)
        return x, new

    return jax.jit(decode, donate_argnums=(2,))


# --------------------------------------------------------------------------
# Per-engine wrappers
# --------------------------------------------------------------------------

class FusedDecodeProgram:
    """A compiled decode tick for one stage configuration.

    Holds the per-run stacked block params (stacked once at build time so
    the tick never re-stacks weights) next to the shared jitted callable.
    """

    def __init__(self, boundaries: tuple[int, ...], fn, run_params,
                 head_params):
        self.boundaries = boundaries
        self.compiled = False        # flips after the first executed tick
        self._fn = fn
        self._run_params = run_params
        self._head_params = head_params

    def _args(self, caches, tok, pos, block_tables):
        tables = () if block_tables is None else (block_tables,)
        return (self._head_params, list(caches), self._run_params, tok,
                pos) + tables

    def step(self, caches: list, tok, pos, block_tables=None):
        """One tick.  ``caches`` is DONATED — adopt the returned list.
        Paged programs additionally take the (B, max_blocks) block tables."""
        nxt, new = self._fn(*self._args(caches, tok, pos, block_tables))
        self.compiled = True
        return nxt, list(new)

    def lower(self, caches: list, tok, pos, block_tables=None):
        """Lower the tick without running it.  Arguments are arrays or
        ShapeDtypeStructs; ``.compile()`` of the result gives the program's
        ``memory_analysis()`` and its HLO text."""
        return self._fn.lower(*self._args(caches, tok, pos, block_tables))


class ExecutorCache:
    """Per-engine front of the process-wide program table.

    ``hits``/``misses`` count configuration lookups from *this* engine
    (the granularity the refactor events report); ``trace_count()`` is the
    process-global retrace counter.
    """

    def __init__(self, cfg: ModelConfig, params: dict, *, max_batch: int,
                 max_seq: int, cache_dtype, prefill_buckets: bool = True,
                 scan_threshold: int = 8, paged: bool = False,
                 paged_kernel: bool = False):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.scan_threshold = scan_threshold
        self.paged = paged
        self.paged_kernel = paged_kernel
        self.cache_dtype = jnp.dtype(cache_dtype)
        self.hits = 0
        self.misses = 0
        self._local: dict = {}
        self._run_params: dict = {}    # (rlo, rhi) -> run param container
        self.head_params = {k: params[k]
                            for k in ("embed", "final_norm", "lm_head",
                                      "pos_embed") if k in params}
        mixers = {cfg.layer_kind(i).mixer for i in range(cfg.n_layers)}
        # bucketed prefill pads the prompt; only valid when padded rows are
        # masked out downstream — true for position-masked attention caches,
        # false for recurrent state (SSM) and ring (sliding-window) caches
        self.can_bucket = (prefill_buckets and not cfg.sliding_window
                           and mixers <= {MIXER_ATTN, MIXER_MLA, MIXER_CROSS})
        # chunked prefill replays chunk n's attention over the cache rows of
        # chunks 0..n-1, so cached rows must hold bit-exact copies of the
        # fresh activations: float32 caches only (a bf16 round-trip breaks
        # greedy parity with whole-prompt prefill), plain attention only
        # (MLA/cross/SSM caches have no chunk-resume path)
        self.can_chunk = (self.can_bucket and mixers == {MIXER_ATTN}
                          and self.cache_dtype == jnp.float32
                          and not any(cfg.layer_kind(i).extra_cross
                                      for i in range(cfg.n_layers)))

    # -- bucketing ---------------------------------------------------------
    def prefill_bucket(self, n: int) -> int:
        """Pad prompt length to a power-of-two bucket (bounds retraces)."""
        if not self.can_bucket:
            return n
        b = 16
        while b < n:
            b *= 2
        return min(b, self.max_seq)

    def chunk_bucket(self, n: int, chunk: int) -> int:
        """Pow2 bucket for a chunk's token count, capped at the chunk size
        (the final, partial chunk of a prompt pads to the next pow2)."""
        b = 16
        while b < n:
            b *= 2
        return min(b, chunk)

    # -- lookups -----------------------------------------------------------
    def _lookup(self, key, builder):
        hit = key in self._local
        if hit:
            self.hits += 1
        else:
            self.misses += 1
            self._local[key] = builder()
        return self._local[key], hit

    def fused_decode(self, boundaries) -> tuple[FusedDecodeProgram, bool]:
        boundaries = tuple(int(b) for b in boundaries)

        def build():
            fn = _shared((self.cfg, "fused", boundaries, self.scan_threshold,
                          self.paged, self.paged_kernel),
                         lambda: _fused_decode_fn(self.cfg, boundaries,
                                                  self.scan_threshold,
                                                  paged=self.paged,
                                                  paged_kernel=self.paged_kernel))
            rp = [self._run_container(rlo, rhi)
                  for lo, hi in _stage_ranges(self.cfg, boundaries)
                  for rlo, rhi in scan_runs(self.cfg, lo, hi)]
            return FusedDecodeProgram(boundaries, fn, rp, self.head_params)

        return self._lookup(("fused", boundaries), build)

    def _run_container(self, rlo: int, rhi: int):
        """Param container for one run, matching ``_fused_decode_fn``'s
        layout (stacked tree for scanned runs, per-layer list / single
        block otherwise).  Cached per (rlo, rhi): configurations that cut
        the model at the same points share the stacked weight copies
        instead of each pinning their own."""
        key = (rlo, rhi)
        if key not in self._run_params:
            blocks = self.params["blocks"]
            if rhi - rlo == 1:
                v = blocks[rlo]
            elif rhi - rlo < self.scan_threshold:
                v = list(blocks[rlo:rhi])
            else:
                v = stack_blocks(blocks[rlo:rhi])
            self._run_params[key] = v
        return self._run_params[key]

    def stage_prefill(self, lo: int, hi: int, *, first: bool, last: bool):
        key = ("prefill", lo, hi, first, last)
        skey = (self.cfg, "prefill", lo, hi, self.max_seq,
                self.cache_dtype.name, first, last, self.paged)
        return self._lookup(key, lambda: _shared(
            skey, lambda: _stage_prefill_fn(self.cfg, lo, hi, self.max_seq,
                                            self.cache_dtype, first, last,
                                            paged=self.paged)))

    def chunk_prefill(self, lo: int, hi: int, *, first: bool, last: bool,
                      sample: bool, chunk_len: int, kv_extent: int):
        """Chunked-prefill program for one stage; ``sample`` only matters on
        the last stage (lm_head + argmax of the prompt's final row), so it
        is masked off elsewhere to maximize program sharing."""
        sample = bool(sample and last)
        key = ("chunk", lo, hi, first, last, sample, chunk_len, kv_extent)
        skey = (self.cfg, "chunk", lo, hi, self.max_seq,
                self.cache_dtype.name, first, last, sample, chunk_len,
                kv_extent, self.paged)
        return self._lookup(key, lambda: _shared(
            skey, lambda: _chunk_prefill_fn(self.cfg, lo, hi, self.max_seq,
                                            self.cache_dtype, first, last,
                                            sample, chunk_len, kv_extent,
                                            paged=self.paged)))

    def stage_decode(self, lo: int, hi: int):
        key = ("decode", lo, hi)
        return self._lookup(key, lambda: _shared(
            (self.cfg, "decode", lo, hi),
            lambda: _stage_decode_fn(self.cfg, lo, hi)))

    def is_warm(self, boundaries) -> bool:
        """Probe (no hit/miss accounting): is this configuration's fused
        program already built AND compiled?  The engine's emergency
        recovery path reports this so benchmarks can attribute recovery
        time to transition vs XLA compile."""
        key = ("fused", tuple(int(b) for b in boundaries))
        prog = self._local.get(key)
        return bool(prog is not None and prog.compiled)

    # -- helpers -----------------------------------------------------------
    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "traces": trace_count()}
