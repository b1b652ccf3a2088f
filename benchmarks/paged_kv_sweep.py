"""Paged KV cache A/B: dense rows vs block pools on the real engine.

Three measurements:

* parity — greedy token streams from the paged engine (gather path AND
  Pallas block-walk kernel) must equal the dense engine's exactly; this
  is the CI gate (``--smoke`` runs only this and asserts).
* concurrency at a fixed HBM budget — give both layouts the same cache
  byte budget (``--hbm-rows`` dense slots' worth, via
  ``dense_slot_bytes``/``block_bytes``) and flood them with short
  requests: dense concurrency is capped at the slot count because every
  slot reserves a full ``max_seq`` row, while the paged pool admits
  while free blocks exist — peak concurrent slots is the paper-facing
  number (cache memory proportional to live tokens).
* equal-batch decode throughput — same batch, dense vs paged tick rate
  on a compute-representative width (the smoke width is pathologically
  attention-dominated; see ``bench_throughput``).  Acceptance: paged
  within 10% of dense.

Writes ``BENCH_paged.json`` at the repo root (override with --out).

    PYTHONPATH=src python benchmarks/paged_kv_sweep.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np


def _engine(arch: str, *, paged: bool, max_batch: int, max_seq: int,
            block_size: int = 16, n_blocks: int = 0,
            paged_kernel: bool = False):
    from repro.configs.base import get_arch
    from repro.models.transformer import init_model
    from repro.serving.engine import (EngineConfig, FlexPipeEngine,
                                      KVCacheConfig, balanced_boundaries)

    cfg = get_arch(arch).smoke_config
    params = init_model(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(max_batch=max_batch, max_seq=max_seq,
                        kv=KVCacheConfig(paged=paged, block_size=block_size,
                                         n_blocks=n_blocks,
                                         paged_kernel=paged_kernel))
    return FlexPipeEngine(cfg, params,
                          balanced_boundaries(cfg.n_layers, 2), ecfg)


def _drain(eng, requests, max_ticks: int):
    """Submit everything at t=0 and tick until drained; returns per-rid
    token streams and the peak number of concurrently active slots."""
    for r in requests:
        assert eng.submit(r, now=0.0).accepted
    hist, peak, now = {}, 0, 0.0
    for _ in range(max_ticks):
        eng.step(now)
        for s in eng.slots:
            if s.request is not None:
                hist[s.request.rid] = list(s.generated)
        peak = max(peak, sum(1 for s in eng.slots if not s.done))
        now += 0.05
        if not len(eng.queue) and all(s.done for s in eng.slots):
            break
    return hist, peak


def bench_parity(arch: str, max_batch: int, max_seq: int) -> dict:
    from repro.serving.workload import Request

    def reqs():
        return [Request(rid=i, arrival=0.0, prompt_len=5 + 3 * i,
                        max_new_tokens=14) for i in range(max_batch + 2)]

    dense, _ = _drain(_engine(arch, paged=False, max_batch=max_batch,
                              max_seq=max_seq), reqs(), 200)
    paged, _ = _drain(_engine(arch, paged=True, max_batch=max_batch,
                              max_seq=max_seq, block_size=8),
                      reqs(), 200)
    kern, _ = _drain(_engine(arch, paged=True, max_batch=max_batch,
                             max_seq=max_seq, block_size=8,
                             paged_kernel=True), reqs(), 200)
    assert dense == paged, "paged (gather) tokens diverge from dense"
    assert dense == kern, "paged (Pallas kernel) tokens diverge from dense"
    return {"requests": len(dense), "paged_matches_dense": True,
            "paged_kernel_matches_dense": True}


def bench_concurrency(arch: str, *, hbm_rows: int, max_seq: int,
                      block_size: int, max_ticks: int) -> dict:
    from repro.configs.base import get_arch
    from repro.models.kvcache import block_bytes, dense_slot_bytes
    from repro.serving.workload import Request

    cfg = get_arch(arch).smoke_config
    import jax.numpy as jnp
    slot_b = dense_slot_bytes(cfg, max_seq, jnp.float32)
    blk_b = block_bytes(cfg, block_size, jnp.float32)
    budget = hbm_rows * slot_b
    n_blocks = budget // blk_b + 1          # +1: reserved null block

    def reqs(n):
        return [Request(rid=i, arrival=0.0, prompt_len=12,
                        max_new_tokens=20) for i in range(n)]

    n_req = 6 * hbm_rows
    dense = _engine(arch, paged=False, max_batch=hbm_rows, max_seq=max_seq)
    dh, dense_peak = _drain(dense, reqs(n_req), max_ticks)
    paged = _engine(arch, paged=True, max_batch=8 * hbm_rows,
                    max_seq=max_seq, block_size=block_size,
                    n_blocks=int(n_blocks))
    ph, paged_peak = _drain(paged, reqs(n_req), max_ticks)
    assert len(dh) == len(ph) == n_req, "a layout failed to drain the burst"
    return {
        "hbm_budget_bytes": int(budget),
        "dense_slot_bytes": int(slot_b),
        "block_bytes": int(blk_b),
        "usable_blocks": int(n_blocks) - 1,
        "dense_max_concurrent": dense_peak,
        "paged_max_concurrent": paged_peak,
        "concurrency_gain": paged_peak / max(dense_peak, 1),
        "paged_preemptions": paged.stats.counters.get("paged_preemptions", 0),
        "paged_peak_frag": paged.stats.block_summary()["max_frag"],
    }


def bench_throughput(arch: str, *, max_batch: int, max_seq: int,
                     ticks: int, repeats: int = 3) -> dict:
    """Equal-batch tick rate, dense vs paged, on a compute-representative
    config.  The smoke config (d_model=64) is pathologically
    attention-dominated — the per-tick block gather is a cache-sized copy
    per layer, so at d_model=64 it is a large fraction of total work; at
    serving-representative widths the MLP/lm_head matmuls dominate and
    the gather is noise.  We widen the model (keeping layer count) so the
    A/B reflects the regime the paper targets.  Each arm times ``repeats``
    back-to-back windows on one warm engine and keeps the best, which
    suppresses scheduler noise on shared CPU runners."""
    import jax.random as jrandom

    from repro.configs.base import get_arch, shrink
    from repro.models.transformer import init_model
    from repro.serving.engine import (EngineConfig, FlexPipeEngine,
                                      KVCacheConfig, balanced_boundaries)
    from repro.serving.workload import Request

    cfg = shrink(get_arch(arch).smoke_config, d_model=256, d_ff=2048,
                 vocab_size=8192)
    params = init_model(jrandom.PRNGKey(0), cfg)

    def run(paged: bool, paged_kernel: bool = False,
            n_ticks: int = ticks, reps: int = repeats) -> dict:
        budget = max_seq - 24
        # all windows must fit in one generation: spin-up + reps windows
        n_ticks = min(n_ticks, (budget - 5 - 3) // reps)
        ecfg = EngineConfig(max_batch=max_batch, max_seq=max_seq,
                            kv=KVCacheConfig(paged=paged, block_size=16,
                                             paged_kernel=paged_kernel))
        eng = FlexPipeEngine(cfg, params,
                             balanced_boundaries(cfg.n_layers, 2), ecfg)
        for i in range(max_batch):
            eng.submit(Request(rid=i, arrival=0.0, prompt_len=12 + i,
                               max_new_tokens=budget), now=0.0)
        eng._admit(0.0)
        for _ in range(3):                   # spin-up: donation steady state
            eng.decode_step(0.0)
        best_dt = None
        for _ in range(reps):
            t0 = time.perf_counter()
            decoded = 0
            for _ in range(n_ticks):
                decoded += eng.step(0.0).decoded   # typed TickReport
            dt = time.perf_counter() - t0
            assert decoded == n_ticks * max_batch, "slots drained mid-window"
            best_dt = dt if best_dt is None else min(best_dt, dt)
        return {"tokens_per_s": n_ticks * max_batch / best_dt,
                "ticks": n_ticks, "windows": reps,
                "tick_ms_best": best_dt / n_ticks * 1e3}

    dense = run(False)
    paged = run(True)
    # The Pallas block-walk kernel only has a compiled path on TPU; off-TPU
    # it runs in interpret mode (python-level grid loop), so time a short
    # window purely as a liveness probe, not a perf number.
    on_tpu = jax.default_backend() == "tpu"
    kern = run(True, paged_kernel=True,
               n_ticks=ticks if on_tpu else min(ticks, 8),
               reps=repeats if on_tpu else 1)
    kern["interpret_mode"] = not on_tpu
    return {
        "batch": max_batch,
        "config": {"d_model": cfg.d_model, "d_ff": cfg.d_ff,
                   "vocab_size": cfg.vocab_size, "n_layers": cfg.n_layers},
        "dense": dense,
        "paged_gather": paged,
        "paged_kernel": kern,
        "paged_vs_dense": paged["tokens_per_s"] / dense["tokens_per_s"],
        "kernel_vs_dense": kern["tokens_per_s"] / dense["tokens_per_s"],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--hbm-rows", type=int, default=4,
                    help="HBM budget expressed in dense max_seq slots")
    ap.add_argument("--ticks", type=int, default=120)
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: parity assert only, tiny shapes")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.smoke:
        parity = bench_parity(args.arch, 4, 64)
        print(json.dumps({"bench": "paged_kv_sweep", "smoke": True,
                          "parity": parity}, indent=2))
        print("\nsmoke OK: paged/dense token parity holds")
        return

    parity = bench_parity(args.arch, args.max_batch, 64)
    conc = bench_concurrency(args.arch, hbm_rows=args.hbm_rows,
                             max_seq=args.max_seq,
                             block_size=args.block_size, max_ticks=4000)
    tput = bench_throughput(args.arch, max_batch=args.max_batch,
                            max_seq=args.max_seq, ticks=args.ticks)
    out = {
        "bench": "paged_kv_sweep",
        "arch": args.arch,
        "block_size": args.block_size,
        "parity": parity,
        "concurrency_at_fixed_hbm": conc,
        "equal_batch_throughput": tput,
        "meta": {"backend": jax.default_backend(), "jax": jax.__version__},
    }
    path = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_paged.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out, indent=2))
    print(f"\nwrote {path}")


if __name__ == "__main__":
    main()
