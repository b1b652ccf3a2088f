"""Executor-cache + fused-hot-path tests: warmed refactors must not trace,
regroup must not copy, fused and unfused paths must agree bit-exactly, and
stage programs must be shared across configurations that cut the model at
the same layer."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.models.transformer import init_model, scan_runs, stack_blocks
from repro.serving.engine import (EngineConfig, FlexPipeEngine,
                                  KVCacheConfig)
from repro.serving.workload import Request


CFG = get_arch("qwen1.5-0.5b").smoke_config
PARAMS = init_model(jax.random.PRNGKey(0), CFG)


def _reqs(n=3, prompt=12, tokens=8):
    return [Request(rid=i, arrival=0.0, prompt_len=prompt + i,
                    max_new_tokens=tokens) for i in range(n)]


def _engine(boundaries, **ecfg_kw):
    kw = dict(max_batch=4, max_seq=64)
    kw.update(ecfg_kw)
    return FlexPipeEngine(CFG, PARAMS, boundaries, EngineConfig(**kw))


class TestExecutorCache:
    def test_warmed_refactor_zero_traces(self):
        """Regression: refactoring between warmed granularity profiles must
        be a pure cache hit — zero new jit traces."""
        eng = _engine([0, 2], warm_profiles=(2, 4))
        for r in _reqs():
            eng.submit(r)
        eng._admit(0.0)
        for t in range(2):
            eng.decode_step(t * 0.1)
        ev = eng.refactor([0, 1, 2, 3])      # == _boundaries_for(4): warmed
        assert ev["compile_cache_hit"] is True
        assert ev["new_traces"] == 0
        ev2 = eng.refactor([0, 2])           # back to the initial config
        assert ev2["compile_cache_hit"] is True
        assert ev2["new_traces"] == 0
        for t in range(2, 4):                # still decoding fine
            assert eng.decode_step(t * 0.1) == 3

    def test_cold_refactor_reports_miss(self):
        eng = _engine([0, 2])
        for r in _reqs():
            eng.submit(r)
        eng._admit(0.0)
        eng.decode_step(0.0)                 # compiles the initial config
        ev = eng.refactor([0, 2, 3])         # never built for this engine
        assert ev["compile_cache_hit"] is False
        ev2 = eng.refactor([0, 2])           # initial config: compiled above
        assert ev2["compile_cache_hit"] is True
        assert ev2["new_traces"] == 0

    def test_registered_but_uncompiled_config_not_reported_as_hit(self):
        """Regression: compile_cache_hit must mean 'compiled', not merely
        'registered' — a refactor back to the never-executed initial config
        pays its compile inside refactor(), not on the next decode tick."""
        eng = _engine([0, 2])                # initial program registered only
        ev = eng.refactor([0, 2, 3])
        assert ev["compile_cache_hit"] is False
        ev2 = eng.refactor([0, 2])           # registered at init, never run
        assert ev2["compile_cache_hit"] is False
        assert ev2["new_traces"] >= 0        # trace may be shared process-wide
        for r in _reqs():
            eng.submit(r)
        eng._admit(0.0)
        import time
        t0 = time.perf_counter()
        eng.decode_step(0.0)                 # must NOT stall on XLA now
        assert time.perf_counter() - t0 < 0.5

    def test_regroup_is_zero_copy(self):
        """Refactoring must not touch per-layer cache buffers (no device
        traffic): every leaf stays the identical array object."""
        eng = _engine([0, 2], warm_profiles=(4,))
        for r in _reqs():
            eng.submit(r)
        eng._admit(0.0)
        eng.decode_step(0.0)
        before = jax.tree.leaves(eng.caches)
        eng.refactor([0, 1, 2, 3])
        after = jax.tree.leaves(eng.caches)
        assert all(a is b for a, b in zip(before, after))

    def test_stage_prefill_shared_across_configs(self):
        """(lo, hi)-keyed programs: a config sharing a cut point reuses the
        already-built stage prefill program (cache hit, not a rebuild)."""
        eng = _engine([0, 2], warm_profiles=())
        for r in _reqs(n=1):
            eng.submit(r)
        eng._admit(0.0)                       # builds prefill for (0,2),(2,4)
        assert ("prefill", 0, 2, True, False) in eng.executors._local
        hits0 = eng.executors.hits
        eng.submit(_reqs(n=1)[0])
        eng.slots[0].done = True              # free the slot
        eng._admit(0.0)                       # same ranges: pure hits
        assert eng.executors.hits > hits0
        assert ("prefill", 0, 2, True, False) in eng.executors._local

    def test_device_resident_sampling_shape(self):
        """The fused tick returns exactly B int32 token ids."""
        eng = _engine([0, 2])
        for r in _reqs():
            eng.submit(r)
        eng._admit(0.0)
        tok = np.zeros((4, 1), np.int32)
        pos = np.array([s.pos if not s.done else 0 for s in eng.slots],
                       np.int32)
        nxt, new = eng._fused.step(eng.caches, jnp.asarray(tok),
                                   jnp.asarray(pos))
        eng.caches = new                      # donated: adopt outputs
        assert nxt.shape == (4,) and nxt.dtype == jnp.int32


class TestFusedBitExactness:
    def _run(self, boundaries, refactor_at=None, new_boundaries=None,
             steps=10, fused=True, warm=(), scan_threshold=8):
        eng = _engine(boundaries, fused_decode=fused, warm_profiles=warm,
                      scan_threshold=scan_threshold)
        for r in _reqs():
            eng.submit(r)
        eng._admit(0.0)
        hist = {}
        for t in range(steps):
            if refactor_at is not None and t == refactor_at:
                eng.refactor(new_boundaries)
            eng.decode_step(t * 0.1)
            for i, s in enumerate(eng.slots):
                if s.generated:
                    hist[i] = list(s.generated)
        return hist, eng

    def test_unbalanced_refactor_bit_exact(self):
        """Refactor to an unbalanced target (stage sizes 2/1/1) mid-decode
        must not change a single token."""
        a, _ = self._run([0, 2])
        b, eng = self._run([0, 2], refactor_at=3, new_boundaries=[0, 2, 3])
        assert a == b
        assert eng.refactor_events[0]["inflight"] == 3

    def test_warmed_refactor_bit_exact(self):
        """A compile-cache-hit refactor produces the same tokens as an
        uninterrupted run."""
        a, _ = self._run([0, 2])
        b, eng = self._run([0, 2], refactor_at=4, new_boundaries=[0, 1, 2, 3],
                           warm=(4,))
        assert a == b
        assert eng.refactor_events[0]["compile_cache_hit"] is True
        assert eng.refactor_events[0]["new_traces"] == 0

    def test_fused_matches_unfused(self):
        """The fused scan+argmax tick is bit-identical to the per-stage
        loop with host-side argmax."""
        a, _ = self._run([0, 2], fused=True)
        b, _ = self._run([0, 2], fused=False)
        assert a == b

    def test_scan_path_bit_exact(self):
        """lax.scan over stacked per-stage block params (threshold 2 forces
        every 2-layer stage through the scan) matches the unrolled tick,
        including across a refactor that changes the run partitioning."""
        a, _ = self._run([0, 2], scan_threshold=8)
        b, _ = self._run([0, 2], scan_threshold=2)
        assert a == b
        c, _ = self._run([0, 2], refactor_at=3, new_boundaries=[0, 1, 2, 3],
                         scan_threshold=2)
        assert a == c

    def test_scan_threshold_one_with_single_layer_runs(self):
        """Regression: scan_threshold=1 ('scan everything') must not crash
        on 1-layer runs — they unroll unconditionally, matching the run
        param containers."""
        a, _ = self._run([0, 2], scan_threshold=8)
        b, _ = self._run([0, 1, 2, 3], scan_threshold=1)
        assert a == b

    def test_unfused_refactor_bit_exact(self):
        a, _ = self._run([0, 2], fused=False)
        b, _ = self._run([0, 2], refactor_at=3, new_boundaries=[0, 1, 2, 3],
                         fused=False)
        assert a == b


class TestEngineConfigHygiene:
    def test_default_config_not_shared(self):
        e1 = FlexPipeEngine(CFG, PARAMS, [0, 2])
        e2 = FlexPipeEngine(CFG, PARAMS, [0, 2])
        assert e1.ecfg is not e2.ecfg
        e1.ecfg.max_batch = 99
        assert e2.ecfg.max_batch != 99

    def test_boundaries_balanced_with_remainder(self):
        eng = _engine([0, 2])
        assert eng._boundaries_for(3) == [0, 2, 3]      # sizes 2,1,1
        assert eng._boundaries_for(4) == [0, 1, 2, 3]
        assert eng._boundaries_for(1) == [0]
        assert eng._boundaries_for(9) == [0, 1, 2, 3]   # clamped to n_layers

    def test_boundaries_balanced_generic(self):
        """Remainder spreads across stages: sizes differ by at most one."""
        from repro.serving.engine import balanced_boundaries
        for L, n in ((26, 4), (26, 5), (32, 6), (7, 3)):
            bs = balanced_boundaries(L, n)
            sizes = [b - a for a, b in zip(bs, bs[1:] + [L])]
            assert len(bs) == n
            assert max(sizes) - min(sizes) <= 1
            assert sum(sizes) == L

    def test_cache_dtype_threaded_from_config(self):
        """No dtype sniffing: EngineConfig.cache_dtype decides every leaf."""
        eng = _engine([0, 2], cache_dtype="bfloat16")
        assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(eng.caches))
        for r in _reqs(n=2):
            eng.submit(r)
        eng._admit(0.0)
        assert eng.decode_step(0.0) == 2
        assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(eng.caches))


SCOPES = ("embed", "attention", "kv_write", "mlp", "head")


def _entry_ops(hlo: str) -> list:
    """(opcode, op_name) of every instruction of the compiled program's
    entry computation."""
    ops = []
    for line in hlo[hlo.index("\nENTRY"):].splitlines()[1:]:
        m = re.search(r" = .*? ([a-z][a-z0-9_-]*)\(", line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            ops.append((m.group(1), name.group(1) if name else ""))
    return ops


def _scope(op_name: str):
    """The innermost of the known scopes in an op's name path (a fused op
    joins its parts' paths with ';': the first decides)."""
    path = op_name.split(";")[0].split("/")
    return next((p for p in reversed(path) if p in SCOPES), None)


@pytest.mark.parametrize("paged,kernel", [(False, False), (True, False),
                                          (True, True)])
def test_tick_ops_carry_named_scopes(paged, kernel):
    """Every op the fused tick's code makes (op_name under ``jit(tick)/``)
    lies in one of the five scopes, and each scope has ops.  Ops that XLA
    adds itself, such as a layout copy of a cache parameter, carry the
    parameter's name or none, and a profile counts them as unscoped."""
    eng = _engine([0, 2], kv=KVCacheConfig(paged=paged, block_size=8,
                                          paged_kernel=kernel))
    B = eng.ecfg.max_batch
    tables = jnp.zeros((B, eng._max_blocks), jnp.int32) if paged else None
    hlo = eng._fused.lower(eng.caches, jnp.zeros((B, 1), jnp.int32),
                           jnp.zeros((B,), jnp.int32),
                           tables).compile().as_text()
    ops = _entry_ops(hlo)
    traced = [(op, n) for op, n in ops if n.startswith("jit(tick)/")]
    assert all(n.startswith("jit(tick)/") for op, n in ops if op == "dot")
    unscoped = [(op, n) for op, n in traced if _scope(n) is None]
    assert unscoped == []
    assert {_scope(n) for _, n in traced} == set(SCOPES)


class TestScanRuns:
    def test_homogeneous_single_run(self):
        assert scan_runs(CFG, 0, 4) == [(0, 4)]
        assert scan_runs(CFG, 1, 3) == [(1, 3)]

    def test_heterogeneous_splits_runs(self):
        cfg = get_arch("gemma3-1b").smoke_config
        runs = scan_runs(cfg, 0, cfg.n_layers)
        assert sum(hi - lo for lo, hi in runs) == cfg.n_layers
        for (a, b), (c, d) in zip(runs, runs[1:]):
            assert b == c
        if cfg.global_every:
            assert len(runs) > 1     # local/global flavors cannot stack

    def test_stack_blocks_roundtrip(self):
        stk = stack_blocks(PARAMS["blocks"][0:2])
        l0 = jax.tree.map(lambda l: l[0], stk)
        ref = PARAMS["blocks"][0]
        assert all(bool((np.asarray(a) == np.asarray(b)).all())
                   for a, b in zip(jax.tree.leaves(l0), jax.tree.leaves(ref)))
