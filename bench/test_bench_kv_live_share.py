"""``kv_live_share`` on runs written out by hand, and against the engine's
own KV row counters on a tiny engine served through the harness's path."""
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness
from bench.harness import ReqRecord, Run, StepRecord
from repro.configs.base import get_arch
from repro.models.transformer import init_model
from repro.serving.engine import EngineConfig, FlexPipeEngine, KVCacheConfig

BENCH = Path(__file__).resolve().parent
read = harness._load_module(BENCH / "metrics/kv_live_share.py").read
rows_held = harness._load_module(BENCH / "metrics/kv_live_share.py").rows_held

DENSE = {"max_batch": 32, "max_seq": 2048, "paged": False, "block_size": 16}
PAGED = {"max_batch": 16, "max_seq": 4096, "paged": True, "block_size": 16}


def _run(serve, steps):
    return Run(cell=None, model={}, serve=serve, seconds=1.0, setup_s=1.0,
               requests=[], steps=list(steps), refactors=[], controller=False)


def test_rows_held_as_the_engine_sizes_them():
    assert rows_held(DENSE) == 32 * 2048
    # one null block plus 256 blocks of 16 rows a slot
    assert rows_held(PAGED) == (1 + 16 * 256) * 16 == 65_552


@pytest.mark.parametrize("serve", [DENSE, PAGED])
def test_share_by_hand(serve):
    steps = [StepRecord(0.0, 0.1, 10, 10 * 700, 0, 0, 0),
             StepRecord(0.1, 0.2, 0, 0, 0, 512, 1),     # prefill only
             StepRecord(0.2, 0.3, 12, 12 * 900, 0, 0, 0)]
    want = 100.0 * (7000 + 10800) / (2 * rows_held(serve))
    assert read(_run(serve, steps)) == pytest.approx(want)
    assert read(_run(serve, steps[1:2])) is None
    assert read(_run(serve, [])) is None


CFG = get_arch("qwen1.5-0.5b").smoke_config
PARAMS = init_model(jax.random.PRNGKey(0), CFG)


@pytest.mark.parametrize("paged", [False, True])
def test_share_equals_the_engine_counters(paged):
    """What the harness stamps from outside the engine gives the same share
    as the engine's ``kv_live_rows`` / ``kv_cache_rows`` over the same
    ticks."""
    kv = KVCacheConfig(paged=paged, block_size=8, paged_kernel=False)
    eng = FlexPipeEngine(CFG, PARAMS, [0, 2],
                         EngineConfig(max_batch=4, max_seq=64, kv=kv))
    srv = harness.Server(eng, None, 0.0, harness.Spans(False),
                         time.perf_counter())
    rng = np.random.default_rng(0)
    for rid, (plen, out) in enumerate([(5, 9), (17, 4), (30, 12), (9, 20),
                                       (12, 6), (3, 15)]):
        srv.submit(ReqRecord(rid, 0.0, rng.integers(0, CFG.vocab_size, plen),
                             out), 0.0)
    while srv.busy():
        srv.step()
    c = eng.stats.counters
    serve = {"max_batch": 4, "max_seq": 64, "paged": paged, "block_size": 8}
    assert rows_held(serve) * c["decode_ticks"] == c["kv_cache_rows"]
    assert sum(1 for s in srv.steps if s.decoded) == c["decode_ticks"]
    assert read(_run(serve, srv.steps)) == pytest.approx(
        100.0 * c["kv_live_rows"] / c["kv_cache_rows"])
