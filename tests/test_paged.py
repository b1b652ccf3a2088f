"""Paged KV cache: allocator properties, paged kernel vs oracle, and
paged-vs-dense bit-exactness of greedy decode through the engine —
steady-state, across an inflight refactor, across a fault-recovery
replay, and across a pool-exhaustion preemption."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.configs.base import get_arch
from repro.core.refactoring import (CacheSnapshot, block_validity,
                                    merge_paged_with_mask)
from repro.kernels.decode_attention import (decode_attention,
                                            paged_decode_attention)
from repro.models.kvcache import (BlockAllocator, blocks_for, can_page,
                                  fragmentation, init_paged_cache)
from repro.models.layers import decode_attention_jnp
from repro.models.transformer import init_model
from repro.serving.engine import EngineConfig, FlexPipeEngine
from repro.serving.workload import Request

KEY = jax.random.PRNGKey(7)
CFG = get_arch("qwen1.5-0.5b").smoke_config
PARAMS = init_model(jax.random.PRNGKey(0), CFG)


# ---------------------------------------------------------------------------
# BlockAllocator properties
# ---------------------------------------------------------------------------

def test_allocator_basic():
    a = BlockAllocator(n_blocks=8, block_size=4)
    assert a.n_usable == 7 and a.n_free == 7          # block 0 reserved
    ids = a.alloc(3)
    assert ids == [1, 2, 3]                            # ascending when fresh
    assert a.n_used == 3 and a.occupancy() == 3 / 7
    assert a.alloc(5) is None and a.n_used == 3        # all-or-nothing
    a.free(ids)
    assert a.n_free == 7 and a.n_used == 0


def test_allocator_lifo_reuse_determinism():
    a = BlockAllocator(n_blocks=8, block_size=4)
    first = a.alloc(4)
    a.free(first)
    # most-recently-freed blocks are reused first, in reversed free order
    assert a.alloc(4) == list(reversed(first))
    b = BlockAllocator(n_blocks=8, block_size=4)
    bf = b.alloc(4)
    b.free(bf)
    assert b.alloc(4) == list(reversed(bf))            # run-to-run identical


def test_allocator_double_free_asserts():
    a = BlockAllocator(n_blocks=4, block_size=4)
    ids = a.alloc(1)
    a.free(ids)
    with pytest.raises(AssertionError):
        a.free(ids)


@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(min_value=-3, max_value=4), min_size=1,
                max_size=40))
def test_allocator_no_leaks(ops):
    """Random submit/complete cycles: every allocation is tracked, frees
    return exactly the allocated ids, and the pool drains to its initial
    free count (no leaked and no conjured blocks)."""
    a = BlockAllocator(n_blocks=12, block_size=4)
    held: list[list[int]] = []
    for op in ops:
        if op > 0:
            ids = a.alloc(op)
            if ids is not None:
                assert len(set(ids)) == op and 0 not in ids
                held.append(ids)
        elif op < 0 and held:
            a.free(held.pop(len(held) % len(held) - 1))
        assert a.n_used + a.n_free == a.n_usable
        assert a.n_used == sum(len(h) for h in held)
    for h in held:
        a.free(h)
    assert a.n_free == a.n_usable and a.n_used == 0


def test_blocks_for_and_fragmentation():
    assert blocks_for(0, 8) == 0
    assert blocks_for(1, 8) == 1
    assert blocks_for(8, 8) == 1
    assert blocks_for(9, 8) == 2
    assert fragmentation(0, 0, 8) == 0.0
    # 9 live tokens in 2 blocks of 8: 7 dead slots / 16 allocated
    assert fragmentation(9, 2, 8) == pytest.approx(7 / 16)


# ---------------------------------------------------------------------------
# Paged kernel vs gathered oracle
# ---------------------------------------------------------------------------

def _paged_setup(B, Kh, hd, bs, M, cache_len, seed=0):
    rng = np.random.default_rng(seed)
    n_blocks = 1 + B * M
    perm = rng.permutation(np.arange(1, n_blocks))
    tables = np.zeros((B, M), np.int32)
    kpool = np.zeros((n_blocks, Kh, bs, hd), np.float32)
    vpool = np.zeros((n_blocks, Kh, bs, hd), np.float32)
    idx = 0
    for b in range(B):
        for j in range(blocks_for(int(cache_len[b]), bs)):
            pid = int(perm[idx]); idx += 1
            tables[b, j] = pid
            kpool[pid] = rng.standard_normal((Kh, bs, hd))
            vpool[pid] = rng.standard_normal((Kh, bs, hd))
    return jnp.asarray(kpool), jnp.asarray(vpool), jnp.asarray(tables)


@pytest.mark.parametrize("B,H,Kh,hd,bs,M,lens", [
    (3, 4, 2, 16, 16, 6, [5, 96, 33]),
    (2, 4, 4, 32, 8, 4, [1, 32]),        # MHA, full tail block
    (1, 8, 2, 16, 32, 3, [70]),          # GQA 4, partial tail
])
def test_paged_kernel_vs_gather(B, H, Kh, hd, bs, M, lens):
    cache_len = np.asarray(lens, np.int32)
    kp, vp, bt = _paged_setup(B, Kh, hd, bs, M, cache_len)
    q = jax.random.normal(KEY, (B, H, hd), jnp.float32)
    out = paged_decode_attention(q, kp, vp, bt, jnp.asarray(cache_len))
    gk = jnp.moveaxis(kp[bt], 2, 1).reshape(B, Kh, M * bs, hd)
    gv = jnp.moveaxis(vp[bt], 2, 1).reshape(B, Kh, M * bs, hd)
    ref = decode_attention_jnp(q[:, None], gk, gv,
                               cache_len=jnp.asarray(cache_len))[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


def test_dense_decode_no_pad_tail():
    """Non-divisible Smax % block_k: the tail block runs out of bounds and
    must still match the oracle (no jnp.pad copy on the hot path)."""
    B, H, Kh, hd, Smax = 2, 4, 2, 16, 100
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (B, Kh, Smax, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (B, Kh, Smax, hd), jnp.float32)
    cl = jnp.asarray([100, 37], jnp.int32)
    ref = decode_attention_jnp(q[:, None], kc, vc, cache_len=cl)[:, 0]
    for bk in (7, 32, 64):
        out = decode_attention(q, kc, vc, cl, block_k=bk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)


def test_resolve_interpret_auto():
    """interpret=None picks the lowering platform's mode: on the CPU that
    is the interpreter, so the program carries no Mosaic call and gives
    exactly what interpret=True gives."""
    cache_len = np.asarray([5, 40], np.int32)
    kp, vp, bt = _paged_setup(2, 2, 16, 8, 6, cache_len)
    q = jax.random.normal(KEY, (2, 4, 16), jnp.float32)
    args = (q, kp, vp, bt, jnp.asarray(cache_len))
    auto = jax.jit(paged_decode_attention)
    assert "tpu_custom_call" not in auto.lower(*args).as_text()
    np.testing.assert_array_equal(
        np.asarray(auto(*args)),
        np.asarray(paged_decode_attention(*args, interpret=True)))


# ---------------------------------------------------------------------------
# Block-granular Eq. 10
# ---------------------------------------------------------------------------

def test_block_validity_mapping():
    bs = 4
    tables = np.array([[1, 2, 3, 0],
                       [4, 0, 0, 0],
                       [5, 6, 0, 0]], np.int32)
    valid = np.array([9, 0, 4], np.int64)   # slot 1 uncovered by snapshot
    bv = block_validity(tables, valid, bs, n_blocks=8)
    assert list(bv) == [0, 4, 4, 1, 0, 4, 0, 0]


def test_merge_paged_with_mask():
    n_blocks, kh, bs, hd = 4, 2, 4, 8
    snap_leaf = jnp.ones((n_blocks, kh, bs, hd))
    live_leaf = jnp.zeros((n_blocks, kh, bs, hd))
    snap = CacheSnapshot(per_layer=[{"mixer": {"k": snap_leaf,
                                               "v": snap_leaf}}],
                         valid_len=None)
    bv = np.array([0, 4, 2, 0])
    out = merge_paged_with_mask(snap, [{"mixer": {"k": live_leaf,
                                                  "v": live_leaf}}], bv)
    k = np.asarray(out[0]["mixer"]["k"])
    assert (k[0] == 0).all()                 # null block: live wins
    assert (k[1] == 1).all()                 # fully valid block: snapshot
    assert (k[2, :, :2] == 1).all() and (k[2, :, 2:] == 0).all()
    assert (k[3] == 0).all()


def test_can_page_and_pool_shapes():
    assert can_page(CFG)
    pools = init_paged_cache(CFG, n_blocks=6, block_size=8)
    assert len(pools) == CFG.n_layers
    kh = CFG.n_kv_heads
    assert pools[0]["mixer"]["k"].shape == (6, kh, 8, CFG.resolved_head_dim)


# ---------------------------------------------------------------------------
# Engine: paged vs dense greedy bit-exactness
# ---------------------------------------------------------------------------

def _run_engine(*, paged, steps=40, refactor_at=None, fail_at=None,
                n_blocks=0, paged_kernel=False, n_req=4, max_new=14):
    ecfg = EngineConfig(max_batch=4, max_seq=64, paged=paged, block_size=8,
                        n_blocks=n_blocks, paged_kernel=paged_kernel,
                        snapshot_interval=4 if fail_at is not None else 0)
    eng = FlexPipeEngine(CFG, PARAMS, [0, 2], ecfg)
    reqs = [Request(rid=i, arrival=0.0, prompt_len=5 + 3 * i,
                    max_new_tokens=max_new) for i in range(n_req)]
    for r in reqs:
        eng.submit(r, now=0.0)
    now, hist = 0.0, {}
    for t in range(steps):
        eng._admit(now)
        if refactor_at is not None and t == refactor_at:
            eng.refactor([0, 1, 3])
        if fail_at is not None and t == fail_at:
            eng._dead.add(0)
            eng.fault_step(now)
        eng.decode_step(now)
        for s in eng.slots:
            if s.request is not None:
                hist[s.request.rid] = list(s.generated)
        now += 0.05
        if eng.stats.completed == n_req and not len(eng.queue):
            break
    return hist, eng


def test_paged_matches_dense_steady_state():
    dense, _ = _run_engine(paged=False)
    paged, eng = _run_engine(paged=True)
    assert dense == paged
    st_ = eng.block_stats()
    assert st_["used_blocks"] == 0 and st_["fragmentation"] == 0.0
    assert eng.stats.block_summary()["max_used"] > 0   # occupancy exported


def test_paged_kernel_matches_dense_greedy():
    dense, _ = _run_engine(paged=False)
    paged, _ = _run_engine(paged=True, paged_kernel=True)
    assert dense == paged


def test_paged_matches_dense_across_refactor():
    dense, _ = _run_engine(paged=False)
    paged, eng = _run_engine(paged=True, refactor_at=7)
    assert dense == paged
    assert eng.refactor_events


def test_paged_matches_dense_across_fault_replay():
    dense, _ = _run_engine(paged=False)
    paged, eng = _run_engine(paged=True, fail_at=9)
    assert dense == paged
    assert eng.recovery_events
    st_ = eng.block_stats()
    assert st_["used_blocks"] == 0                 # recovery leaked nothing


def test_pool_exhaustion_preempts_and_recovers():
    """A pool far smaller than the dense footprint forces preemptions;
    requeued requests regenerate bit-identical text (greedy), everyone
    completes, and the pool drains back to empty."""
    dense, _ = _run_engine(paged=False, steps=60)
    paged, eng = _run_engine(paged=True, steps=400, n_blocks=9)
    assert eng.stats.counters.get("paged_preemptions", 0) > 0
    assert eng.stats.completed == 4
    assert dense == paged
    assert eng.block_stats()["used_blocks"] == 0


def test_paged_requires_divisible_max_seq():
    with pytest.raises(AssertionError):
        FlexPipeEngine(CFG, PARAMS, [0, 2],
                       EngineConfig(max_batch=2, max_seq=65, paged=True,
                                    block_size=8))
