"""Engine tests: live inflight refactoring preserves generation exactly;
continuous batching with ragged admission; Eq. 10 validity-mask merge."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.core.refactoring import merge_with_mask, snapshot
from repro.models.kvcache import init_cache, migration_plan
from repro.models.transformer import init_model
from repro.serving.engine import (EngineConfig, FlexPipeEngine,
                                  KVCacheConfig)
from repro.serving.workload import Request


CFG = get_arch("qwen1.5-0.5b").smoke_config
PARAMS = init_model(jax.random.PRNGKey(0), CFG)


def _reqs(n=3, prompt=12, tokens=8):
    return [Request(rid=i, arrival=0.0, prompt_len=prompt + i,
                    max_new_tokens=tokens) for i in range(n)]


def _run(boundaries, refactor_at=None, new_boundaries=None, steps=10):
    eng = FlexPipeEngine(CFG, PARAMS, boundaries,
                         EngineConfig(max_batch=4, max_seq=64))
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


@pytest.mark.parametrize("paged", [False, True])
def test_kv_row_counters_match_the_slots(paged):
    """Each decode tick adds the rows its decoding slots attend over (their
    cache length after the tick's write) to ``kv_live_rows`` and the rows
    the cache holds to ``kv_cache_rows``."""
    eng = FlexPipeEngine(CFG, PARAMS, [0, 2], EngineConfig(
        max_batch=4, max_seq=64, kv=KVCacheConfig(paged=paged,
                                                  block_size=8)))
    held = eng.ecfg.n_blocks * 8 if paged else 4 * 64
    for r in _reqs(tokens=5):
        eng.submit(r)
    eng._admit(0.0)
    c = eng.stats.counters
    live = cache = 0
    for t in range(7):                   # the requests finish at tick 4
        decoding = [i for i, s in enumerate(eng.slots)
                    if not s.done and s.generated]
        n = eng.decode_step(t * 0.1)
        assert n == len(decoding)
        if n:
            live += sum(eng.slots[i].pos for i in decoding)
            cache += held
        assert c.get("decode_ticks", 0) == min(t + 1, 4)
        assert c.get("kv_live_rows", 0) == live
        assert c.get("kv_cache_rows", 0) == cache
    assert live == sum(p + k for p in (12, 13, 14) for k in range(1, 5))
    assert eng.stats.kv_summary() == {
        "decode_ticks": 4, "mean_live_rows": live / 4,
        "live_share": live / cache}


class TestInflightRefactoring:
    def test_tokens_identical_across_split(self):
        a, _ = _run([0, 2])
        b, eng = _run([0, 2], refactor_at=3, new_boundaries=[0, 1, 2, 3])
        assert a == b
        assert eng.refactor_events[0]["inflight"] == 3

    def test_tokens_identical_across_merge(self):
        a, _ = _run([0, 1, 2, 3])
        b, _ = _run([0, 1, 2, 3], refactor_at=4, new_boundaries=[0, 2])
        assert a == b

    def test_multiple_refactorings(self):
        a, _ = _run([0, 2], steps=12)
        eng = FlexPipeEngine(CFG, PARAMS, [0, 2],
                             EngineConfig(max_batch=4, max_seq=64))
        for r in _reqs():
            eng.submit(r)
        eng._admit(0.0)
        hist = {}
        for t in range(12):
            if t == 2:
                eng.refactor([0, 1, 2, 3])
            if t == 5:
                eng.refactor([0, 3])
            if t == 8:
                eng.refactor([0, 1, 2, 3])
            eng.decode_step(t * 0.1)
            for i, s in enumerate(eng.slots):
                if s.generated:
                    hist[i] = list(s.generated)
        assert a == hist

    def test_all_requests_complete(self):
        eng = FlexPipeEngine(CFG, PARAMS, [0, 2],
                             EngineConfig(max_batch=2, max_seq=64))
        reqs = _reqs(n=5, tokens=4)            # more requests than slots
        stats = eng.run(reqs, time_per_tick=0.05)
        assert stats.completed == 5


class TestConsistencyProtocol:
    def test_migration_plan_counts_moved_layers(self):
        moves = migration_plan([0, 2], [0, 1, 2, 3], 4)
        # layer ownership: old {0,1}->s0, {2,3}->s1; new one layer per stage
        assert (1, 0, 1) in moves and (3, 1, 3) in moves
        assert migration_plan([0, 2], [0, 2], 4) == []

    def test_merge_with_mask_eq10(self):
        """Tokens before valid_len come from the snapshot; later tokens from
        the live cache; O(1) state takes the live value."""
        cache = init_cache(CFG, 1, 16, jnp.float32)
        snap_val = jax.tree.map(lambda x: jnp.ones_like(x), cache)
        live_val = jax.tree.map(lambda x: 2 * jnp.ones_like(x), cache)
        sn = snapshot(snap_val, valid_len=5)
        merged = merge_with_mask(sn, live_val, live_len=9)
        k = merged[0]["mixer"]["k"]            # (B, Kh, Smax, hd)
        assert float(k[0, 0, 4, 0]) == 1.0     # pre-snapshot token
        assert float(k[0, 0, 5, 0]) == 2.0     # decoded in flight


class TestLauncher:
    def test_build_engine_published_dtype_and_warm_refactor(self):
        """``build_engine`` (the launcher's and chip smoke's constructor)
        serves in the dtype it is given, weights and cache alike, starts at
        the first stage count and warms every one it lists."""
        from repro.launch.serve import build_engine
        eng = build_engine(CFG, jnp.bfloat16, max_batch=2, max_seq=64,
                           stages=(2, 4))
        assert eng.boundaries == [0, 2]
        assert {l.dtype for l in jax.tree.leaves(eng.params)} \
            == {jnp.dtype(jnp.bfloat16)}
        assert {l.dtype for l in jax.tree.leaves(eng.caches)} \
            == {jnp.dtype(jnp.bfloat16)}
        for r in _reqs(n=2, tokens=4):
            eng.submit(r, now=0.0)
        eng.step(0.0)
        ev = eng.refactor([0, 1, 2, 3])
        assert ev["inflight"] == 2
        assert ev["compile_cache_hit"] and ev["new_traces"] == 0
        now = 0.05
        while any(not s.done for s in eng.slots):
            eng.step(now)
            now += 0.05
        assert eng.stats.completed == 2

    @pytest.mark.parametrize("from_env", [True, False],
                             ids=["env", "checkout"])
    def test_compile_cache_placement(self, tmp_path, monkeypatch, from_env):
        """JAX_COMPILATION_CACHE_DIR wins and is left to JAX; without it the
        cache goes to a fixed directory inside the checkout that git
        ignores.  Compiled programs land in the chosen directory."""
        from jax.experimental.compilation_cache import compilation_cache
        from repro.launch import serve
        repo = serve.COMPILE_CACHE_DIR.parent
        before = jax.config.jax_compilation_cache_dir
        min_time = jax.config.jax_persistent_cache_min_compile_time_secs
        try:
            if from_env:
                monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
                assert serve.enable_compile_cache() == str(tmp_path)
                assert jax.config.jax_compilation_cache_dir == before
                # what JAX does with the variable when it starts
                jax.config.update("jax_compilation_cache_dir", str(tmp_path))
                jax.config.update(
                    "jax_persistent_cache_min_compile_time_secs", 0)
                compilation_cache.reset_cache()
                jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
                assert list(tmp_path.iterdir())
            else:
                monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR",
                                   raising=False)
                path = serve.enable_compile_cache()
                assert path == str(repo / ".jax_cache")
                assert jax.config.jax_compilation_cache_dir == path
                assert ".jax_cache/" in (repo / ".gitignore").read_text()
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              min_time)
            compilation_cache.reset_cache()
