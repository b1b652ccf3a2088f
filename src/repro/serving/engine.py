"""FlexPipe serving engine — the REAL JAX data plane.

Disaggregated per-stage execution (DESIGN.md §3): each pipeline stage is a
jitted program over its contiguous layer range; the engine moves activations
between stages and performs *live inflight refactoring*: re-grouping stage
boundaries (and every in-flight request's KV cache) between generation steps
without dropping a request.  Tokens decoded across a refactoring event are
bit-identical to an uninterrupted run (tested in tests/test_engine.py).

Hot path
--------
The steady-state decode tick is a single XLA dispatch per configuration
(``ExecutorCache.fused_decode``): embed -> every stage (layer loop as
``lax.scan`` over stacked per-stage block params) -> lm_head -> on-device
argmax.  Only the B sampled token ids (int32) cross to host per tick;
EOS / length bookkeeping is vectorized in numpy.  Prefill admission writes
the prompt's cache rows directly into the batch slot with
``jax.lax.dynamic_update_slice`` inside a donated per-stage program — no
host-side temp-cache scatter.

Donation invariants
-------------------
All executor programs donate their cache arguments: after a decode tick or
a prefill, the cache buffers previously held in ``self.caches`` are consumed
and must not be touched again — the engine adopts the returned buffers.
Never hold references to engine cache leaves across a tick.

Refactoring fast path
---------------------
Per-layer cache buffers are the canonical state; a refactor only re-views
them under new stage ownership (zero-copy list re-slicing — no device
traffic) and swaps in the target configuration's fused program from the
executor cache.  ``refactor()`` reports ``compile_cache_hit`` and
``new_traces`` so benchmarks can separate transition stall from XLA
compilation; ``EngineConfig.warm_profiles`` precompiles all granularity
profiles at engine start so steady-state refactors never trace.

Continuous batching: fixed slot array; per-slot cache length (ragged decode
through the position-vector path in models/layers.py).

Placement: every stage program runs on one device (the default one); no
stage is pinned to chips of its own yet.  Stages on separate chips are
ROADMAP item R2.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.refactoring import (CacheSnapshot, block_validity,
                                    merge_paged_with_mask, merge_with_mask,
                                    snapshot)
from repro.models.kvcache import (BlockAllocator, blocks_for, can_page,
                                  fragmentation, group_by_stage, init_cache,
                                  init_paged_cache)
from repro.models.model import embed_tokens, lm_head
from repro.serving.admission import (ADMITTED, PRIO_STANDARD, REJECTED,
                                     AdmissionConfig, AdmissionQueue,
                                     CostModel)
from repro.serving.executor_cache import ExecutorCache, trace_count
from repro.serving.faults import (COMM_TRANSIENT, OOM, PREEMPT_STAGE,
                                  SLOWDOWN)
from repro.serving.metrics import ServingStats
from repro.serving.tracing import span
from repro.serving.workload import Request


def balanced_boundaries(n_layers: int, n_stages: int) -> list[int]:
    """Balanced stage starts: remainder layers spread one-per-stage across
    the leading stages (never dumped onto the last stage)."""
    n = max(1, min(n_stages, n_layers))
    base, rem = divmod(n_layers, n)
    out = [0]
    for i in range(n - 1):
        out.append(out[-1] + base + (1 if i < rem else 0))
    return out


@dataclass
class KVCacheConfig:
    """KV-cache layout knobs (vLLM-style paging; ``paged=False`` keeps the
    dense ``max_batch x max_seq`` row layout).

    Paged mode uses per-layer block pools + per-slot block tables: memory
    scales with live tokens, admission gates on free blocks, and completed
    slots return their blocks to the pool.  Requires fused_decode, an
    attention-only pattern (``can_page``), and ``max_seq % block_size == 0``
    (keeps the paged logical view the same shape as a dense cache — the
    bit-exactness invariant the tests pin)."""
    paged: bool = False
    block_size: int = 16
    # physical blocks in the pool; 0 = auto-size to the dense footprint
    # (max_batch * max_seq tokens) plus the reserved null block
    n_blocks: int = 0
    # decode attention over the pools: False = gather the logical view and
    # reuse the dense decode math (bit-identical to dense); True = Pallas
    # block-table-walk kernel (kernels/decode_attention.py)
    paged_kernel: bool = False


@dataclass
class PrefillConfig:
    """Prefill scheduling knobs.

    ``chunk`` > 0 arms chunked continuous-batching prefill: each admitted
    prompt is split into ``chunk``-token pieces (pow2, >= 16; the final
    partial piece pads to its own pow2 bucket) and at most ``budget``
    bucketed prompt tokens are pumped per engine tick, round-robin across
    mid-prefill slots, while decode slots keep emitting tokens.  Greedy
    outputs are bit-identical to whole-prompt prefill (the chunk programs
    pin their attention reduction extent to the whole prompt's bucket).
    Falls back to whole-prompt prefill when the architecture can't chunk
    (non-attention mixers, sliding windows, or a non-float32 cache).
    """
    buckets: bool = True    # pad prompts to pow2 buckets (when safe)
    chunk: int = 0          # tokens per prefill chunk (0 = whole-prompt)
    budget: int = 0         # max bucketed prompt tokens per tick (0 = chunk)


_LEGACY_KV = {"paged": "paged", "block_size": "block_size",
              "n_blocks": "n_blocks", "paged_kernel": "paged_kernel"}
_LEGACY_PREFILL = {"prefill_buckets": "buckets", "prefill_chunk": "chunk",
                   "prefill_budget": "budget"}


class EngineConfig:
    """Engine configuration: scalar knobs plus typed sub-configs.

    ``kv`` (KVCacheConfig) owns the cache layout, ``prefill``
    (PrefillConfig) the prefill scheduler, and ``admission``
    (AdmissionConfig, serving/admission.py) the overload protection.
    The pre-redesign flat kwargs (``paged=``, ``block_size=``,
    ``n_blocks=``, ``paged_kernel=``, ``prefill_buckets=``) are still
    accepted with a DeprecationWarning and forwarded into the sub-configs;
    the flat names stay readable as properties so old call sites keep
    working unchanged.
    """

    def __init__(self, max_batch: int = 8, max_seq: int = 256,
                 cache_dtype: str = "float32", eos_token: int = -1,
                 control_interval: float = 1.0, fused_decode: bool = True,
                 scan_threshold: int = 8,
                 warm_profiles: tuple[int, ...] = (),
                 snapshot_interval: int = 0,
                 admission: Optional[AdmissionConfig] = None,
                 kv: Optional[KVCacheConfig] = None,
                 prefill: Optional[PrefillConfig] = None, **legacy):
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.cache_dtype = cache_dtype
        self.eos_token = eos_token               # -1: run to max_new_tokens
        self.control_interval = control_interval  # controller cadence (sim s)
        self.fused_decode = fused_decode         # single-dispatch decode tick
        # layer runs at least this deep execute as a stacked lax.scan
        # (compile time lever); shallower runs unroll for in-place donated
        # cache updates
        self.scan_threshold = scan_threshold
        # granularity profiles (stage counts) to precompile at engine start
        # so refactoring between them never traces; () = compile lazily
        self.warm_profiles = warm_profiles
        # Eq. 10 snapshot cadence in decode ticks (0 = off): every
        # interval-th tick the engine copies the per-layer caches + per-slot
        # valid lengths to a host-side CacheSnapshot, bounding the replay
        # delta after a stage preemption to at most `snapshot_interval` ticks
        self.snapshot_interval = snapshot_interval
        # overload protection (serving/admission.py): None keeps the legacy
        # unbounded FIFO; an AdmissionConfig arms bounded admission, EDF
        # ordering, deadline shedding, KV watermarks, brownout degradation
        self.admission = admission
        self.kv = kv if kv is not None else KVCacheConfig()
        self.prefill = prefill if prefill is not None else PrefillConfig()
        for k, v in legacy.items():
            if k in _LEGACY_KV:
                warnings.warn(
                    f"EngineConfig({k}=...) is deprecated; pass "
                    f"kv=KVCacheConfig({_LEGACY_KV[k]}=...) instead",
                    DeprecationWarning, stacklevel=2)
                setattr(self.kv, _LEGACY_KV[k], v)
            elif k in _LEGACY_PREFILL:
                warnings.warn(
                    f"EngineConfig({k}=...) is deprecated; pass "
                    f"prefill=PrefillConfig({_LEGACY_PREFILL[k]}=...) "
                    "instead", DeprecationWarning, stacklevel=2)
                setattr(self.prefill, _LEGACY_PREFILL[k], v)
            else:
                raise TypeError(
                    f"EngineConfig got an unexpected keyword {k!r}")
        c = self.prefill.chunk
        if c:
            if c < 16 or (c & (c - 1)):
                raise ValueError(
                    f"prefill chunk must be a power of two >= 16, got {c}")
            if self.max_seq % c:
                raise ValueError(
                    f"max_seq ({self.max_seq}) must be a multiple of the "
                    f"prefill chunk ({c}) so chunk starts never cross the "
                    "prompt bucket (bit-exactness invariant)")

    # -- flat views of the nested knobs (pre-redesign call sites) --------
    @property
    def paged(self) -> bool:
        return self.kv.paged

    @property
    def block_size(self) -> int:
        return self.kv.block_size

    @property
    def n_blocks(self) -> int:
        return self.kv.n_blocks

    @property
    def paged_kernel(self) -> bool:
        return self.kv.paged_kernel

    @property
    def prefill_buckets(self) -> bool:
        return self.prefill.buckets


@dataclass(frozen=True)
class SubmitResult:
    """Typed verdict from ``Engine.submit``: truthy iff the request was
    enqueued; ``reason`` carries the admission verdict string (ADMITTED /
    REJECTED) and ``queue_depth`` the post-submit depth."""
    accepted: bool
    reason: str
    queue_depth: int

    def __bool__(self) -> bool:
        return self.accepted


@dataclass(frozen=True)
class TickReport:
    """Typed result of one ``Engine.step``: what the tick actually did."""
    now: float
    decoded: int           # tokens emitted by decode slots this tick
    prefill_tokens: int    # bucketed prompt tokens pumped through chunks
    prefilling: int        # slots still mid-prefill after the tick
    admitted: int          # requests assigned to slots this tick
    completed: int         # requests finished this tick
    queue_depth: int       # queue depth after the tick
    recoveries: int        # emergency recoveries performed this tick


@dataclass
class Slot:
    request: Optional[Request] = None
    pos: int = 0                     # valid cache length
    generated: list = field(default_factory=list)
    done: bool = True
    budget: int = 0                  # token budget clamped to fit max_seq
    prompt: Optional[np.ndarray] = None  # admitted prompt (replay source)


class FlexPipeEngine:
    def __init__(self, cfg: ModelConfig, params: dict,
                 boundaries: list[int], ecfg: Optional[EngineConfig] = None):
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg if ecfg is not None else EngineConfig()
        self.boundaries = list(boundaries)
        self.stats = ServingStats()
        self.refactor_events: list[dict] = []
        self.cache_dtype = (jnp.float32 if self.ecfg.cache_dtype == "float32"
                            else jnp.bfloat16)
        # paged-KV state (None/empty in dense mode)
        self.allocator: Optional[BlockAllocator] = None
        self.block_tables: Optional[np.ndarray] = None
        self._slot_blocks: list[list[int]] = []
        self._snap_tables: Optional[np.ndarray] = None
        if self.ecfg.paged:
            assert can_page(cfg), \
                "paged KV needs an attention-only, non-windowed pattern"
            assert self.ecfg.fused_decode, "paged KV requires fused_decode"
            assert self.ecfg.max_seq % self.ecfg.block_size == 0, \
                "max_seq must be a multiple of block_size (bit-exactness)"
            bs = self.ecfg.block_size
            self._max_blocks = self.ecfg.max_seq // bs   # table width per slot
            if self.ecfg.n_blocks <= 0:
                self.ecfg.kv.n_blocks = \
                    1 + self.ecfg.max_batch * self._max_blocks
            self.allocator = BlockAllocator(self.ecfg.n_blocks, bs)
            self.block_tables = np.zeros(
                (self.ecfg.max_batch, self._max_blocks), np.int32)
            self._slot_blocks = [[] for _ in range(self.ecfg.max_batch)]
        # canonical state: per-layer cache list (dense: batch rows; paged:
        # block pools shared across the batch)
        self.caches = self._init_caches()
        # KV rows the cache holds, live or not (the kv_cache_rows counter)
        self._cache_rows = (
            self.ecfg.n_blocks * self.ecfg.block_size if self.ecfg.paged
            else self.ecfg.max_batch * self.ecfg.max_seq)
        self.slots = [Slot() for _ in range(self.ecfg.max_batch)]
        # overload protection: with an AdmissionConfig the queue IS the
        # bounded EDF AdmissionQueue (list-compatible for len/append);
        # without one it stays the legacy unbounded FIFO list
        self.admission: Optional[AdmissionQueue] = None
        if self.ecfg.admission is not None:
            self.admission = AdmissionQueue(self.ecfg.admission,
                                            stats=self.stats)
            self.queue = self.admission
        else:
            self.queue: list[Request] = []
        self.executors = ExecutorCache(
            cfg, params, max_batch=self.ecfg.max_batch,
            max_seq=self.ecfg.max_seq, cache_dtype=self.cache_dtype,
            prefill_buckets=self.ecfg.prefill_buckets,
            scan_threshold=self.ecfg.scan_threshold,
            paged=self.ecfg.paged, paged_kernel=self.ecfg.paged_kernel)
        self._fused = None
        if self.ecfg.fused_decode:
            self._fused, _ = self.executors.fused_decode(tuple(self.boundaries))
        # chunked continuous-batching prefill: armed only when both the
        # config asks for it AND the architecture supports bit-exact
        # chunking (attention-only, unwindowed, float32 cache)
        self._chunk = 0
        self._prefill_rr = 0          # round-robin cursor over prefill slots
        if self.ecfg.prefill.chunk:
            if self.executors.can_chunk:
                self._chunk = self.ecfg.prefill.chunk
            else:
                warnings.warn(
                    "prefill.chunk requested but this architecture cannot "
                    "chunk bit-exactly (needs attention-only mixers, no "
                    "sliding window, float32 cache); falling back to "
                    "whole-prompt prefill", stacklevel=2)
        # fault-tolerance state (armed via attach_faults)
        self.faults = None               # FaultInjector
        self.fault_policy = None         # FaultPolicy
        self.health = None               # StageHealthMonitor
        self.recovery_events: list[dict] = []
        self.failed_requests: list[Request] = []
        self._snapshot: Optional[CacheSnapshot] = None
        self._snap_rids: list = []
        self._dead: set[int] = set()
        self._slowdowns: dict[int, tuple[float, float]] = {}
        self._tick_count = 0
        if self.ecfg.warm_profiles:
            self.warmup(self.ecfg.warm_profiles)

    # ------------------------------------------------------------------
    def _init_caches(self, layers=None) -> list:
        """Fresh per-layer cache list in the engine's layout (dense rows or
        paged block pools)."""
        if self.ecfg.paged:
            return init_paged_cache(self.cfg, self.ecfg.n_blocks,
                                    self.ecfg.block_size, self.cache_dtype,
                                    layers=layers)
        return init_cache(self.cfg, self.ecfg.max_batch, self.ecfg.max_seq,
                          self.cache_dtype, layers=layers)

    def _tables_dev(self):
        """Device copy of the block tables for this tick (paged only)."""
        return jnp.asarray(self.block_tables) if self.ecfg.paged else None

    # ------------------------------------------------------------------
    def _stage_ranges(self) -> list[tuple[int, int]]:
        ends = self.boundaries[1:] + [self.cfg.n_layers]
        return list(zip(self.boundaries, ends))

    @property
    def stage_caches(self) -> list[list]:
        """Per-stage re-view of the per-layer caches (zero-copy slicing)."""
        return group_by_stage(self.caches, self.boundaries)

    def warmup(self, stage_counts: tuple[int, ...] = ()) -> dict:
        """Precompile executors for the given granularity profiles (stage
        counts) plus the current configuration.

        Rotates ONE donated dummy cache through every configuration's
        decode program, so warm-up costs a single extra cache allocation
        and one throwaway tick per profile — after it, refactoring between
        warmed profiles performs zero jit traces.  Each configuration's
        stage-prefill programs are also compiled at the base prompt bucket
        (larger pow2 buckets still trace lazily on first admission; on
        non-bucketable archs prompt lengths are unbounded, so prefill always
        compiles lazily).
        """
        t0 = time.perf_counter()
        traces0 = trace_count()
        keys = [tuple(self.boundaries)]
        for n in stage_counts:
            k = tuple(self._boundaries_for(n))
            if k not in keys:
                keys.append(k)
        B = self.ecfg.max_batch
        tok = jnp.zeros((B, 1), jnp.int32)
        pos = jnp.zeros((B,), jnp.int32)
        dummy = self._init_caches()
        # warm ticks run over all-null block tables: writes land in the
        # reserved null block, never in live pool state
        wt = (jnp.zeros((B, self._max_blocks), jnp.int32)
              if self.ecfg.paged else None)
        out = None
        for k in keys:
            if self.ecfg.fused_decode:
                prog, _ = self.executors.fused_decode(k)
                out, dummy = prog.step(dummy, tok, pos, wt)
            else:
                x = jnp.zeros((B, 1, self.cfg.d_model),
                              self.params["embed"].dtype)
                ends = list(k[1:]) + [self.cfg.n_layers]
                for lo, hi in zip(k, ends):
                    fn, _ = self.executors.stage_decode(lo, hi)
                    x, new = fn(self.params["blocks"][lo:hi], x,
                                dummy[lo:hi], pos, None)
                    dummy[lo:hi] = new
                out = x
        for k in keys:
            self._warm_prefill(list(k))
        if out is not None:
            jax.block_until_ready(out)
        return {"configs": len(keys), "t": time.perf_counter() - t0,
                "new_traces": trace_count() - traces0}

    def _warm_prefill(self, boundaries: list[int]) -> None:
        """Compile a configuration's stage-prefill programs at the smallest
        prompt bucket so the first admission after a refactor doesn't stall
        the tick loop on XLA (bucketable archs only)."""
        if not self.executors.can_bucket:
            return
        S0 = self.executors.prefill_bucket(1)
        ends = boundaries[1:] + [self.cfg.n_layers]
        ranges = list(zip(boundaries, ends))
        out = jnp.zeros((1, S0), jnp.int32)
        slot_ix = (jnp.zeros((1, self._max_blocks), jnp.int32)
                   if self.ecfg.paged else jnp.zeros((), jnp.int32))
        true_len = jnp.asarray(1, jnp.int32)
        for si, (lo, hi) in enumerate(ranges):
            fn, _ = self.executors.stage_prefill(
                lo, hi, first=(si == 0), last=(si == len(ranges) - 1))
            dummy = self._init_caches(layers=range(lo, hi))
            out, _ = fn(self.params["blocks"][lo:hi],
                        self.executors.head_params, out, dummy, slot_ix,
                        true_len, None)
        jax.block_until_ready(out)

    def refactor(self, new_boundaries: list[int]) -> dict:
        """Inflight refactoring: re-group stage boundaries + caches (Eq. 10).

        In-flight requests keep their slots and positions.  Per-layer cache
        buffers are untouched (zero-copy re-view under the new ownership);
        the target configuration's fused program comes from the executor
        cache — a hit costs a dict lookup, a miss compiles eagerly here
        (reported via ``compile_cache_hit`` / ``new_traces``) so the decode
        loop never stalls on XLA mid-stream."""
        with span("engine.refactor"):
            t0 = time.perf_counter()
            old = list(self.boundaries)
            traces0 = trace_count()
            self.boundaries = list(new_boundaries)
            hit = True
            if self.ecfg.fused_decode:
                self._fused, registered = self.executors.fused_decode(
                    tuple(self.boundaries))
                # a program registered but never executed still owes its jit
                # trace+compile: pay it here, not on the next decode tick, and
                # report the hit only when it was genuinely compiled already
                hit = registered and self._fused.compiled
                if not self._fused.compiled:
                    self._compile_fused(self._fused)
            else:
                missed = []
                for lo, hi in self._stage_ranges():
                    fn, h = self.executors.stage_decode(lo, hi)
                    hit = hit and h
                    if not h:
                        missed.append((lo, hi, fn))
                if missed:
                    self._compile_stages(missed)
            ev = {"t": time.perf_counter() - t0, "from": old,
                  "to": list(new_boundaries),
                  "inflight": sum(1 for s in self.slots if not s.done),
                  "compile_cache_hit": hit,
                  "new_traces": trace_count() - traces0}
            self.refactor_events.append(ev)
            return ev

    def _compile_fused(self, prog) -> None:
        """Force trace+compile off the decode stream via a throwaway tick on
        a donated dummy cache (the engine's live caches are never touched)."""
        B = self.ecfg.max_batch
        dummy = self._init_caches()
        wt = (jnp.zeros((B, self._max_blocks), jnp.int32)
              if self.ecfg.paged else None)
        nxt, _ = prog.step(dummy, jnp.zeros((B, 1), jnp.int32),
                           jnp.zeros((B,), jnp.int32), wt)
        jax.block_until_ready(nxt)

    def _compile_stages(self, missed: list) -> None:
        """Eagerly trace+compile missed per-stage decode programs on dummy
        caches so the unfused decode loop never stalls on XLA mid-stream."""
        B = self.ecfg.max_batch
        pos = jnp.zeros((B,), jnp.int32)
        x = jnp.zeros((B, 1, self.cfg.d_model), self.params["embed"].dtype)
        for lo, hi, fn in missed:
            dummy = init_cache(self.cfg, B, self.ecfg.max_seq,
                               self.cache_dtype, layers=range(lo, hi))
            out, _ = fn(self.params["blocks"][lo:hi], x, dummy, pos, None)
            jax.block_until_ready(out)

    # ------------------------------------------------------------------
    # Fault tolerance: detection, emergency inflight refactor, replay
    # ------------------------------------------------------------------
    def attach_faults(self, injector=None, policy=None, monitor=None) -> None:
        """Arm the fault stack (serving/faults.py): a FaultInjector that
        schedules preemption/OOM/comm/slowdown events, a FaultPolicy for
        request timeout/retry/degradation, and a StageHealthMonitor whose
        heartbeats + tick watchdog drive detection."""
        self.faults = injector
        self.fault_policy = policy
        self.health = monitor
        if monitor is not None:
            monitor.reset(len(self.boundaries), 0.0)

    def _maybe_snapshot(self) -> None:
        """Periodic Eq. 10 snapshot: host-side copy of the per-layer caches
        with each slot's committed-token count as its validity horizon."""
        iv = self.ecfg.snapshot_interval
        if not iv:
            return
        self._tick_count += 1
        if self._tick_count % iv:
            return
        pos = np.array([0 if s.done else s.pos for s in self.slots],
                       np.int64)
        if not pos.any():
            return
        self._snapshot = snapshot(self.caches, pos)
        self._snap_rids = [s.request.rid if (not s.done and s.request)
                           else None for s in self.slots]
        # paged: the snapshot-time tables map each slot's valid tokens to
        # physical blocks.  Block allocation is append-only while a slot is
        # active, so these tables are a prefix of the live ones at restore
        # time for any rid-matching slot.
        self._snap_tables = (self.block_tables.copy()
                             if self.ecfg.paged else None)

    def fault_step(self, now: float) -> list[dict]:
        """Pre-tick fault handling: poll injected events, beat surviving
        stages, and run detection + emergency recovery.  Called by run()
        before every decode tick (and usable from manual tick loops)."""
        recs: list[dict] = []
        if self.faults is None and not self._dead:
            return recs
        if self.faults is not None:
            for ev in self.faults.poll(now):
                n_stages = len(self.boundaries)
                self.stats.bump("faults_injected")
                self.stats.fault_log.append((now, ev.kind, ev.detail))
                if ev.kind in (PREEMPT_STAGE, OOM):
                    self.stats.bump("preemptions" if ev.kind == PREEMPT_STAGE
                                    else "oom_events")
                    self._dead.add(ev.stage % n_stages)
                elif ev.kind == COMM_TRANSIENT:
                    # transient send/recv failure: the tick is retransmitted
                    # transparently; no state is lost
                    self.stats.bump("comm_errors")
                elif ev.kind == SLOWDOWN:
                    self.stats.bump("slowdowns")
                    self._slowdowns[ev.stage % n_stages] = (
                        now + ev.duration, ev.factor)
        if not self._dead:
            return recs
        # detection: dead stages miss their heartbeat window; with no
        # monitor attached the dispatch failure itself is the detector
        if self.health is not None:
            for s in range(len(self.boundaries)):
                if s not in self._dead:
                    self.health.heartbeat(s, now)
            detected = [s for s in self.health.dead_stages(now)
                        if s in self._dead]
        else:
            detected = sorted(self._dead)
        if detected:
            recs.append(self._on_stage_failure(detected, now,
                                               reason="preemption"))
        return recs

    def health_step(self, now: float, tick_wall_s: float) -> Optional[dict]:
        """Post-tick watchdog: observe the decode tick's wall time (scaled
        by any injected slowdown) and gracefully migrate away from a
        straggling stage once the patience threshold trips."""
        if self.health is None:
            return None
        slow = [(s, f) for s, (until, f) in self._slowdowns.items()
                if until > now]
        factor = max((f for _, f in slow), default=1.0)
        verdict = self.health.observe_tick(tick_wall_s * factor)
        if verdict == "straggler" and slow:
            return self._migrate_from_straggler(slow[0][0], now)
        return None

    def _migrate_from_straggler(self, stage: int, now: float) -> dict:
        """Llumnix-style graceful migration: the straggling stage is still
        reachable, so its KV moves with the refactor (zero-copy regroup) —
        no replay, no lost rows, outputs bit-identical."""
        t0 = time.perf_counter()
        n_new = max(len(self.boundaries) - 1, 1)
        ev = self.refactor(self._boundaries_for(n_new))
        ev["emergency"] = True
        ev["reason"] = "straggler"
        self._slowdowns.clear()
        if self.health is not None:
            self.health.reset(len(self.boundaries), now)
        rec = {"t": now, "kind": "graceful_migration", "stage": stage,
               "reason": "straggler", "recovery_s": time.perf_counter() - t0,
               "refactor": ev, "replayed_ticks": 0,
               "compile_cache_hit": ev["compile_cache_hit"],
               "new_traces": ev["new_traces"]}
        self.stats.bump("graceful_migrations")
        self.stats.record_recovery(rec["recovery_s"], t=now,
                                   kind="graceful_migration")
        self.recovery_events.append(rec)
        return rec

    def _on_stage_failure(self, stages: list[int], now: float,
                          reason: str = "preemption") -> dict:
        """Emergency inflight refactor after stage preemption (KV lost).

        detect -> refactor -> restore -> replay: the failed stages' layer
        caches are dropped (that memory is gone), boundaries re-partition
        around the surviving stage budget (warm profiles mean zero-retrace
        recovery), committed rows are restored from the latest Eq. 10
        snapshot via merge_with_mask, and only the delta decoded since the
        snapshot is replayed.  Slots not covered by the snapshot re-prefill
        their full history from valid_len=0.  No committed token is ever
        lost: the generated text lives host-side in the slots."""
        t0 = time.perf_counter()
        B = self.ecfg.max_batch
        ranges = self._stage_ranges()
        stages = sorted({min(max(s, 0), len(ranges) - 1) for s in stages})
        lost_layers = [li for s in stages for li in range(*ranges[s])]
        for s in stages:                  # that device memory is gone
            lo, hi = ranges[s]
            self.caches[lo:hi] = self._init_caches(layers=range(lo, hi))
        n_new = max(len(ranges) - len(stages), 1)
        nb = self._boundaries_for(n_new)
        was_warm = self.executors.is_warm(nb)
        ev = self.refactor(nb)
        ev["emergency"] = True
        ev["reason"] = reason
        # Eq. 10 restore: committed rows < valid[i] come from the snapshot,
        # anything newer keeps the live value (surviving stages) or the
        # zeros just written (lost stages -> replayed below)
        valid = np.zeros(B, np.int64)
        if self._snapshot is not None:
            snap_pos = np.asarray(self._snapshot.valid_len)
            for i, s in enumerate(self.slots):
                if not s.done and s.request is not None \
                        and i < len(self._snap_rids) \
                        and self._snap_rids[i] == s.request.rid:
                    valid[i] = min(int(snap_pos[i]), s.pos)
            if valid.any():
                if self.ecfg.paged:
                    # block-granular Eq. 10: map each covered slot's valid
                    # horizon through the snapshot-time tables to per-
                    # physical-block token counts (uncovered slots have
                    # valid=0, so their freed-and-reused blocks stay live)
                    bv = block_validity(self._snap_tables, valid,
                                        self.ecfg.block_size,
                                        self.ecfg.n_blocks)
                    self.caches = merge_paged_with_mask(
                        CacheSnapshot(self._snapshot.per_layer, valid),
                        self.caches, bv)
                else:
                    live_len = int(max(s.pos for s in self.slots
                                       if not s.done))
                    self.caches = merge_with_mask(
                        CacheSnapshot(self._snapshot.per_layer, valid),
                        self.caches, live_len)
        replayed = self._replay(valid)
        dt = time.perf_counter() - t0
        rec = {"t": now, "kind": "emergency_refactor", "reason": reason,
               "stages_lost": stages, "layers_lost": lost_layers,
               "recovery_s": dt, "refactor": ev, "was_warm": was_warm,
               "replayed_ticks": replayed,
               "compile_cache_hit": ev["compile_cache_hit"],
               "new_traces": ev["new_traces"]}
        self.stats.bump("emergency_refactors")
        self.stats.bump("replayed_ticks", replayed)
        self.stats.record_recovery(dt, t=now, kind="emergency_refactor",
                                   detail=reason)
        self.recovery_events.append(rec)
        self._dead.clear()
        self._slowdowns.clear()
        if self.health is not None:
            self.health.reset(len(self.boundaries), now)
        return rec

    def _replay(self, valid: np.ndarray) -> int:
        """Replay committed tokens through the decode path to rebuild lost
        cache rows: slot i replays positions [valid[i], pos) — the delta
        since the snapshot, or its full history when valid[i] == 0.

        Replay feeds the SAME tokens at the SAME positions through the
        (refactored) decode program, so rebuilt rows are bit-identical to
        the originals for snapshot-covered slots; sampled outputs are
        discarded (the committed text is already host-side).

        A chunked mid-prefill slot's history is the prompt prefix its
        cursor has committed (``prompt[:pos]``); its remaining chunks run
        normally after recovery.  Slots with ``pos == 0`` (assigned but no
        chunk committed yet) have no rows to rebuild and are skipped —
        their batch rows take the idle row-0 write, which chunk 0
        overwrites."""
        active = [i for i, s in enumerate(self.slots)
                  if not s.done and s.pos > 0]
        if not active:
            return 0
        B = self.ecfg.max_batch
        hist = {}
        for i in active:
            s = self.slots[i]
            if s.generated:
                h = np.concatenate([
                    np.asarray(s.prompt, dtype=np.int64),
                    np.asarray(s.generated[:-1], dtype=np.int64)])
            else:
                h = np.asarray(s.prompt[:s.pos], dtype=np.int64)
            assert len(h) == s.pos, "history must cover committed rows"
            hist[i] = h
        cursor = {i: int(valid[i]) for i in active}
        ticks = 0
        # replay never allocates blocks (rebuilt rows land in blocks the
        # slots already own), so one table upload covers every tick below
        tables = self._tables_dev()
        while any(cursor[i] < self.slots[i].pos for i in active):
            tok = np.zeros((B, 1), np.int32)
            pos = np.zeros((B,), np.int32)
            for i in active:
                # caught-up slots idempotently rewrite their last row
                p = min(cursor[i], self.slots[i].pos - 1)
                tok[i, 0] = hist[i][p]
                pos[i] = p
            if self._fused is not None:
                # paged replay routes through the LIVE tables (a superset
                # of the snapshot-time tables for covered slots), so
                # rebuilt rows land in the blocks the slot already owns
                _, new = self._fused.step(self.caches, jnp.asarray(tok),
                                          jnp.asarray(pos), tables)
                self.caches = new
            else:
                self._decode_unfused(tok, pos)
            for i in active:
                cursor[i] = min(cursor[i] + 1, self.slots[i].pos)
            ticks += 1
        return ticks

    def _apply_fault_policy(self, now: float) -> None:
        """Request-level timeout/retry/degradation (FaultPolicy)."""
        pol = self.fault_policy
        if pol is None:
            return
        for si, s in enumerate(self.slots):
            if s.done or s.request is None:
                continue
            req = s.request
            started = req.start if req.start >= 0 else now
            if now - started <= pol.timeout_s:
                continue
            # abort this attempt; committed partial output is discarded
            s.done = True
            s.request = None
            s.generated = []
            s.pos = 0
            self._free_slot_blocks(si)
            req.attempts += 1
            self.stats.bump("timeouts")
            if pol.should_retry(req.attempts):
                self.stats.bump("retries")
                req.retry_at = now + pol.backoff(req.attempts)
                # per-attempt queue accounting restarts at the requeue
                req.enqueued_at = now
                if pol.degrade_last_attempt \
                        and pol.is_last_attempt(req.attempts):
                    req.max_new_tokens = pol.degraded_budget(
                        req.max_new_tokens)
                    req.degraded = True
                    self.stats.bump("degraded")
                self.queue.append(req)
            else:
                req.failed = True
                req.fail_reason = f"timeout after {req.attempts} attempts"
                self.stats.bump("request_failures")
                self.failed_requests.append(req)

    # ------------------------------------------------------------------
    def submit(self, req: Request, now: Optional[float] = None) -> SubmitResult:
        """Enqueue a request.  With admission control armed this is the
        bounded fast-fail point: a full queue rejects immediately (the
        503 path — no prefill work is ever spent on a rejected request).

        Returns a typed ``SubmitResult`` (truthy iff enqueued; the old
        ADMITTED/REJECTED sentinel survives as ``.reason``)."""
        t = req.arrival if now is None else now
        if self.admission is not None:
            verdict = self.admission.submit(req, t)
            reason = (ADMITTED if verdict == ADMITTED
                      else (getattr(req, "fail_reason", "") or REJECTED))
            return SubmitResult(verdict == ADMITTED, reason, len(self.queue))
        req.enqueued_at = t
        self.queue.append(req)
        return SubmitResult(True, ADMITTED, len(self.queue))

    @property
    def rejected_requests(self) -> list[Request]:
        return self.admission.rejected if self.admission is not None else []

    @property
    def shed_requests(self) -> list[Request]:
        return self.admission.shed if self.admission is not None else []

    def kv_used_frac(self) -> float:
        """Fraction of KV capacity committed by active requests — the
        quantity the admission watermarks gate on.  Paged mode reports the
        block pool's occupancy (real footprint); dense mode approximates
        it with committed slot rows over total rows."""
        if self.ecfg.paged:
            return self.allocator.occupancy()
        used = sum(s.pos for s in self.slots if not s.done)
        return used / float(self.ecfg.max_batch * self.ecfg.max_seq)

    # -- paged block lifecycle -----------------------------------------
    def _free_slot_blocks(self, i: int) -> None:
        """Return slot i's blocks to the pool and null out its table row
        (every completion/abort/preemption path funnels through here)."""
        if not self.ecfg.paged:
            return
        if self._slot_blocks[i]:
            self.allocator.free(self._slot_blocks[i])
            self._slot_blocks[i] = []
        self.block_tables[i, :] = 0

    def _alloc_for_slot(self, i: int, n: int) -> bool:
        """Append n physical blocks to slot i's table (all-or-nothing)."""
        ids = self.allocator.alloc(n)
        if ids is None:
            return False
        base = len(self._slot_blocks[i])
        self.block_tables[i, base:base + n] = ids
        self._slot_blocks[i].extend(ids)
        return True

    def _block_need(self, req: Request) -> int:
        """Blocks a request needs at admission: its (truncated) prompt plus
        the first decode write — further growth allocates per tick."""
        plen = (len(req.prompt_tokens) if hasattr(req, "prompt_tokens")
                else req.prompt_len)
        S = min(plen, max(1, self.ecfg.max_seq - req.max_new_tokens - 1))
        return blocks_for(S + 1, self.ecfg.block_size)

    def _pick_victim(self) -> int:
        """Preemption victim on pool exhaustion: the lowest-priority live
        slot (largest priority class value), breaking ties by most blocks
        held (frees the most pool) and then by highest slot index — fully
        deterministic, so requeue order (and therefore greedy regeneration)
        is reproducible."""
        live = [i for i, s in enumerate(self.slots) if not s.done]
        return max(live, key=lambda i: (
            getattr(self.slots[i].request, "priority", PRIO_STANDARD)
            if self.slots[i].request is not None else PRIO_STANDARD,
            len(self._slot_blocks[i]), i))

    def _ensure_decode_blocks(self, now: float) -> None:
        """Grow each active slot's table to cover this tick's write
        position; on pool exhaustion a victim slot is preempted (blocks
        freed, request requeued — greedy decode regenerates identically).
        The victim is chosen by ``_pick_victim`` (lowest priority / most
        blocks), not simply whichever slot's tail allocation failed."""
        for i, s in enumerate(self.slots):
            if s.done:
                continue
            if s.pos // self.ecfg.block_size < len(self._slot_blocks[i]):
                continue
            while not self._alloc_for_slot(i, 1):
                victim = self._pick_victim()
                self._preempt_slot(victim, now)
                if victim == i:
                    break              # the requester itself lost the tie

    def _preempt_slot(self, i: int, now: float) -> None:
        s = self.slots[i]
        req = s.request
        self._free_slot_blocks(i)
        s.done = True
        s.request = None
        s.generated = []
        s.pos = 0
        s.prompt = None
        self.stats.bump("paged_preemptions")
        if req is not None:
            req.enqueued_at = now
            req.retry_at = now
            self.queue.append(req)

    def block_stats(self) -> dict:
        """Pool occupancy for dashboards/benchmarks (paged mode only)."""
        if not self.ecfg.paged:
            return {}
        live = sum(s.pos for s in self.slots if not s.done)
        used = self.allocator.n_used
        return {"used_blocks": used, "free_blocks": self.allocator.n_free,
                "occupancy": self.allocator.occupancy(),
                "fragmentation": fragmentation(live, used,
                                               self.ecfg.block_size)}

    def _admit(self, now: float) -> int:
        """Fill free slots from the queue; returns #requests assigned.

        With chunked prefill armed, admission only *assigns* the slot (its
        chunks are pumped by ``_prefill_step``); otherwise the whole prompt
        prefills here, as before."""
        admitted = 0
        for slot_id, slot in enumerate(self.slots):
            if not slot.done or not len(self.queue):
                continue
            if self.admission is not None:
                fits = ((lambda r: self.allocator.can_alloc(
                    self._block_need(r))) if self.ecfg.paged else None)
                req = self.admission.pop_admissible(now, self.kv_used_frac(),
                                                    fits=fits)
                if req is None:
                    break
                # brownout: shrink the token budget by priority class
                f = self.admission.budget_factor(req.priority)
                if f < 1.0:
                    req.max_new_tokens = max(int(req.max_new_tokens * f), 1)
                    req.degraded = True
                    self.stats.bump("brownout_degraded")
            else:
                # retried requests wait out their backoff before re-admission
                j = next((k for k, r in enumerate(self.queue)
                          if r.retry_at <= now), None)
                if j is None:
                    break
                if self.ecfg.paged and not self.allocator.can_alloc(
                        self._block_need(self.queue[j])):
                    break              # wait for completions to free blocks
                req = self.queue.pop(j)
            req.start = now
            # per-attempt queue wait: measured from THIS attempt's enqueue
            # time, never spanning earlier failed attempts
            since = req.enqueued_at if req.enqueued_at >= 0 else req.arrival
            req.queue_wait = max(now - since, 0.0)
            if self._chunk:
                if self._assign_slot(slot_id, req, now):
                    admitted += 1
            else:
                self._prefill_into_slot(slot_id, req, now)
                admitted += 1
        return admitted

    def _truncate_prompt(self, req: Request) -> tuple[np.ndarray, int]:
        """Admitted prompt and clamped decode budget: the prompt truncates
        (keeping >= 1 token) so prompt + generated tokens fit max_seq."""
        prompt = np.asarray(req.prompt_tokens) \
            if hasattr(req, "prompt_tokens") \
            else np.arange(req.prompt_len) % self.cfg.vocab_size
        prompt = prompt[: max(1, self.ecfg.max_seq - req.max_new_tokens - 1)]
        budget = min(req.max_new_tokens,
                     self.ecfg.max_seq - int(prompt.shape[0]) - 1)
        return prompt, budget

    def _assign_slot(self, slot_id: int, req: Request, now: float) -> bool:
        """Chunked admission: bind the request to the slot and set its
        prefill cursor to zero — no model work happens here.  ``slot.pos``
        doubles as the cursor (it always counts committed cache rows), and
        ``generated == []`` marks the slot as mid-prefill."""
        prompt, budget = self._truncate_prompt(req)
        S = int(prompt.shape[0])
        if self.ecfg.paged:
            # all blocks for the prompt + first decode write are claimed up
            # front: chunk scatters and parked decode writes both stay
            # inside the slot's own blocks
            if not self._alloc_for_slot(
                    slot_id, blocks_for(S + 1, self.ecfg.block_size)):
                req.enqueued_at = now       # pool raced empty: requeue
                req.retry_at = now
                self.queue.append(req)
                return False
        slot = self.slots[slot_id]
        slot.request = req
        slot.prompt = prompt.astype(np.int64)
        slot.pos = 0
        slot.generated = []
        slot.budget = budget
        slot.done = False
        return True

    def _prefill_step(self, now: float) -> int:
        """Pump pending prefill chunks, round-robin across mid-prefill
        slots, spending at most ``prefill.budget`` bucketed prompt tokens
        (default: one chunk's worth) — the decode tick that follows keeps
        running for every slot that already has tokens.  Returns the
        bucketed token count actually spent."""
        if not self._chunk:
            return 0
        pending = [i for i, s in enumerate(self.slots)
                   if not s.done and not s.generated]
        if not pending:
            return 0
        budget = self.ecfg.prefill.budget or self._chunk
        # rotate the starting slot so equal-length prompts share the budget
        # fairly instead of the lowest slot always going first
        start = self._prefill_rr % len(pending)
        ring = pending[start:] + pending[:start]
        self._prefill_rr += 1
        spent = 0
        while ring and spent < budget:
            i = ring.pop(0)
            with span("engine.prefill_chunk", rid=self.slots[i].request.rid):
                spent += self._prefill_chunk_into(i, now)
            s = self.slots[i]
            if not s.done and not s.generated:
                ring.append(i)         # more chunks pending: back of line
        return spent

    def _prefill_chunk_into(self, slot_id: int, now: float) -> int:
        """Run ONE prefill chunk for the slot: commit rows [pos, pos+L) of
        the prompt through every stage's chunk program.  The final chunk
        samples the first token (TTFT stamps here) and flips the slot into
        decode; short requests whose budget is already spent finish
        immediately, exactly like whole-prompt prefill."""
        s = self.slots[slot_id]
        req = s.request
        S = len(s.prompt)
        c0 = s.pos
        L = min(self._chunk, S - c0)
        Lb = self.executors.chunk_bucket(L, self._chunk)
        Sp = self.executors.prefill_bucket(S)
        final = c0 + L >= S
        toks = np.zeros((1, Lb), np.int32)
        toks[0, :L] = s.prompt[c0:c0 + L]
        ranges = self._stage_ranges()
        out = jnp.asarray(toks)
        slot_ix = (jnp.asarray(self.block_tables[slot_id:slot_id + 1])
                   if self.ecfg.paged else jnp.asarray(slot_id, jnp.int32))
        pos0 = jnp.asarray(c0, jnp.int32)
        last_ix = jnp.asarray(S - 1 - c0, jnp.int32)
        memory = getattr(req, "memory", None)
        for si, (lo, hi) in enumerate(ranges):
            fn, _ = self.executors.chunk_prefill(
                lo, hi, first=(si == 0), last=(si == len(ranges) - 1),
                sample=final, chunk_len=Lb, kv_extent=Sp)
            out, new = fn(self.params["blocks"][lo:hi],
                          self.executors.head_params, out,
                          self.caches[lo:hi], slot_ix, pos0, last_ix, memory)
            self.caches[lo:hi] = new
        s.pos = c0 + L
        self.stats.bump("prefill_chunks")
        if final:
            # only the final chunk samples; its one token must reach the
            # host to seed s.generated for the decode loop
            with span("engine.sync"):
                # repro: noqa[JIT102] -- intended one-token sync (last chunk)
                first = int(np.asarray(out)[0])      # first sampled token
            req.first_token = now                    # TTFT: this chunk
            s.generated = [first]
            eos = self.ecfg.eos_token
            if s.budget <= 1 or (eos >= 0 and first == eos):
                req.finish = now
                self.stats.record(now, req.latency, req.met_slo,
                                  queue_s=req.queue_wait,
                                  ttft_s=req.first_token - req.arrival)
                s.done = True
                s.request = None
                self._free_slot_blocks(slot_id)
        return Lb

    def _prefill_into_slot(self, slot_id: int, req: Request,
                           now: float = 0.0) -> None:
        prompt, budget = self._truncate_prompt(req)
        S = int(prompt.shape[0])
        Sp = self.executors.prefill_bucket(S)
        with span("engine.prefill", rid=req.rid, bucket=Sp):
            if self.ecfg.paged:
                # blocks for the prompt + the first decode write; bucket
                # padding beyond them scatters into the null block
                if not self._alloc_for_slot(
                        slot_id, blocks_for(S + 1, self.ecfg.block_size)):
                    req.enqueued_at = now       # pool raced empty: requeue
                    req.retry_at = now
                    self.queue.append(req)
                    return
            toks = np.zeros((1, Sp), np.int32)
            toks[0, :S] = prompt
            memory = getattr(req, "memory", None)
            ranges = self._stage_ranges()
            out = jnp.asarray(toks)
            slot_ix = (jnp.asarray(self.block_tables[slot_id:slot_id + 1])
                       if self.ecfg.paged else jnp.asarray(slot_id, jnp.int32))
            true_len = jnp.asarray(S, jnp.int32)
            for si, (lo, hi) in enumerate(ranges):
                fn, _ = self.executors.stage_prefill(
                    lo, hi, first=(si == 0), last=(si == len(ranges) - 1))
                out, new = fn(self.params["blocks"][lo:hi],
                              self.executors.head_params, out,
                              self.caches[lo:hi], slot_ix, true_len, memory)
                self.caches[lo:hi] = new
            slot = self.slots[slot_id]
            slot.request = req
            slot.pos = S
            slot.prompt = prompt.astype(np.int64)
            slot.budget = budget
            with span("engine.sync"):
                # repro: noqa[JIT102] -- intended one-token sync ending prefill
                first = int(np.asarray(out)[0])      # first sampled token
            req.first_token = now                    # TTFT: prefill emits it
            slot.generated = [first]
            slot.done = False
            eos = self.ecfg.eos_token
            if budget <= 1 or (eos >= 0 and first == eos):
                # budget already exhausted by the prefill's token: finish now
                # rather than letting the next tick overshoot max_new_tokens
                req.finish = now
                self.stats.record(now, req.latency, req.met_slo,
                                  queue_s=req.queue_wait,
                                  ttft_s=req.first_token - req.arrival)
                slot.done = True
                slot.request = None
                self._free_slot_blocks(slot_id)

    # ------------------------------------------------------------------
    def decode_step(self, now: float) -> int:
        """One decode tick for all active slots; returns #active.

        Fused path: one XLA dispatch for embed + all stages + lm_head +
        argmax; the engine's caches are donated and replaced by the tick's
        outputs, and only B int32 token ids come back to host.  Each tick
        bumps the KV row counters: ``decode_ticks``, ``kv_live_rows`` (rows
        the decoding slots attend over, their new row included) and
        ``kv_cache_rows`` (rows the cache holds, live or not)."""
        B = self.ecfg.max_batch
        with span("engine.decode.prepare"):
            if self.ecfg.paged:
                # tail-block growth happens BEFORE the active mask is read:
                # a slot the pool can't grow is preempted and skips this tick
                self._ensure_decode_blocks(now)
            live = np.array([not s.done for s in self.slots])
            gen = np.array([len(s.generated) for s in self.slots])
            # mid-prefill slots (chunked: no sampled token yet) don't decode
            active = live & (gen > 0)
            n_active = int(active.sum())
            if not n_active:
                return 0
            tok = np.zeros((B, 1), np.int32)
            pos = np.zeros((B,), np.int32)
            if self._chunk:
                # the fused tick writes a KV row for EVERY batch slot; park a
                # mid-prefill slot's garbage write on its next chunk's first
                # row (pos), which that chunk overwrites — never on row 0,
                # where it would clobber the slot's committed chunk 0
                for i in np.nonzero(live & ~active)[0]:
                    pos[i] = self.slots[i].pos
            for i in np.nonzero(active)[0]:
                s = self.slots[i]
                tok[i, 0] = s.generated[-1]
                pos[i] = s.pos
            if self._fused is not None:
                args = (jnp.asarray(tok), jnp.asarray(pos), self._tables_dev())
        if self._fused is not None:
            with span("engine.decode.dispatch"):
                nxt_dev, new = self._fused.step(self.caches, *args)
                self.caches = new
            with span("engine.sync"):
                # repro: noqa[JIT102] -- THE per-tick sync: one B-int32 copy
                nxt = np.asarray(nxt_dev)
        else:
            with span("engine.decode.dispatch"):
                nxt = self._decode_unfused(tok, pos)
        with span("engine.decode.bookkeep"):
            # EOS / length bookkeeping, vectorized in numpy
            lim = np.array([s.budget if s.request else 0 for s in self.slots])
            eos = self.ecfg.eos_token
            hit_eos = (eos >= 0) & (nxt == eos)
            finished = active & ((gen + 1 >= lim) | hit_eos)
            for i in np.nonzero(active)[0]:
                s = self.slots[i]
                s.generated.append(int(nxt[i]))
                s.pos += 1
            for i in np.nonzero(finished)[0]:
                s = self.slots[i]
                req = s.request
                req.finish = now
                self.stats.record(now, req.latency, req.met_slo,
                                  queue_s=req.queue_wait,
                                  ttft_s=req.first_token - req.arrival)
                s.done = True
                s.request = None
                self._free_slot_blocks(i)
            rows = pos + active              # rows each slot holds after it
            self.stats.bump("decode_ticks")
            self.stats.bump("kv_live_rows", int(rows[active].sum()))
            self.stats.bump("kv_cache_rows", self._cache_rows)
            if self.ecfg.paged:
                used = self.allocator.n_used
                self.stats.record_blocks(
                    used, self.allocator.n_free,
                    fragmentation(int(rows[live & ~finished].sum()), used,
                                  self.ecfg.block_size))
            self._maybe_snapshot()
        return n_active

    def _decode_unfused(self, tok: np.ndarray, pos: np.ndarray) -> np.ndarray:
        """Per-stage decode loop (pre-fusion path, kept for benchmarking
        before/after and as a fallback): one dispatch per stage plus a
        host-side argmax over full logits."""
        x = embed_tokens(self.cfg, self.params, jnp.asarray(tok),
                         pos0=jnp.asarray(pos))
        pos_v = jnp.asarray(pos)
        for lo, hi in self._stage_ranges():
            fn, _ = self.executors.stage_decode(lo, hi)
            x, new = fn(self.params["blocks"][lo:hi], x, self.caches[lo:hi],
                        pos_v, None)
            self.caches[lo:hi] = new
        logits = lm_head(self.cfg, self.params, x)[:, -1, :]
        # repro: noqa[JIT102] -- unfused fallback's intended per-tick sync
        return np.asarray(jnp.argmax(logits, axis=-1))

    # ------------------------------------------------------------------
    def step(self, now: float) -> TickReport:
        """One full engine tick: fault policy -> admission maintenance ->
        slot fill -> fault detection/recovery -> prefill chunks -> decode.

        This is the typed driver the benchmarks and ``run()`` use; manual
        loops that only need decode can keep calling ``decode_step``
        (whole-prompt prefill still happens inside ``_admit``)."""
        with span("engine.step"):
            completed0 = self.stats.completed
            with span("engine.faults"):
                self._apply_fault_policy(now)
            with span("engine.admit"):
                if self.admission is not None:
                    # shed already-dead queued work even while slots are
                    # full, then advance the brownout controller on
                    # saturation
                    self.admission.expire(now)
                    self.admission.update(now)
                admitted = self._admit(now)
            with span("engine.faults"):
                recs = self.fault_step(now)
            prefill_tokens = self._prefill_step(now)
            if self.health is None:
                decoded = self.decode_step(now)
            else:
                t_tick = time.perf_counter()
                decoded = self.decode_step(now)
                with span("engine.faults"):
                    self.health_step(now, time.perf_counter() - t_tick)
            return TickReport(
                now=now, decoded=decoded, prefill_tokens=prefill_tokens,
                prefilling=sum(1 for s in self.slots
                               if not s.done and not s.generated),
                admitted=admitted,
                completed=self.stats.completed - completed0,
                queue_depth=len(self.queue), recoveries=len(recs))

    def run(self, requests: list[Request], controller=None,
            time_per_tick: float = 0.05) -> ServingStats:
        """Trace-driven loop in simulated time; controller may refactor."""
        pending = sorted(requests, key=lambda r: r.arrival)
        if self.admission is not None and self.admission.cost.auto:
            # sim-time serving: a prefill costs one admission tick (or,
            # chunked, budget-many prompt tokens per tick) and decode one
            # tick per token — seed the shedding cost model
            self.admission.cost.seed_from_tick(
                time_per_tick,
                prefill_tokens_per_tick=(
                    (self.ecfg.prefill.budget or self._chunk)
                    if self._chunk else 0))
        now = 0.0
        last_ctl = 0.0
        i = 0
        while i < len(pending) or len(self.queue) or \
                any(not s.done for s in self.slots):
            while i < len(pending) and pending[i].arrival <= now:
                self.submit(pending[i], now=pending[i].arrival)
                if controller is not None:
                    controller.on_request(pending[i].arrival)
                i += 1
            self.step(now)
            if controller is not None and now - last_ctl >= self.ecfg.control_interval:
                last_ctl = now
                sat = self.admission.saturation() \
                    if self.admission is not None else 0.0
                d, _ = controller.control_step(now, len(self.queue),
                                               saturation=sat)
                if d.changed and d.target.stages <= self.cfg.n_layers:
                    nb = self._boundaries_for(d.target.stages)
                    if nb != self.boundaries:
                        self.refactor(nb)
            self.stats.queue_samples.append((now, len(self.queue)))
            if self.admission is not None:
                self.stats.record_saturation(now,
                                             self.admission.saturation())
            now += time_per_tick
        return self.stats

    def _boundaries_for(self, n_stages: int) -> list[int]:
        return balanced_boundaries(self.cfg.n_layers, n_stages)
