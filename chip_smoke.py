"""Chip smoke test: the FlexPipe engine serving qwen1.5-0.5b at its published
widths (24 layers, d 1024, 16 heads of 64, ff 2816, V 151,936) in bf16 on a
TPU, with random weights from a seed.

    python chip_smoke.py            # one chip: dense and paged serving
    python chip_smoke.py --chips 4  # only the four-stage shard_map pipeline

One chip: the engine is built through ``repro.launch.serve.build_engine``
with 2- and 6-stage granularities precompiled, serves 12 requests (prompts
of 32 to 512 tokens, 32 new tokens each) through ``submit``/``step`` with a
live refactor from 2 to 6 stages while they are in flight, and every emitted
token is checked against a float32 teacher-forced reference.  The same
requests are then served with the paged KV cache and the Pallas table-walk
kernel, whose compiled tick must hold the Mosaic call.

Four chips: ``parallel/pipeline.py``'s prefill and decode steps with four
stages on a (1, 4) mesh, compared with the single-device model on chip 0.

Every check raises on failure, so the process exits non-zero and the last
line is not printed.  The last line of stdout is the JSON contract line
``{"ok": true, "device": {...}}``.  The times printed are smoke readings of
one run, not benchmark results.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ARCH = "qwen1.5-0.5b"
SEED = 0                 # weights, prompts and pipeline tokens
MAX_BATCH = 8
MAX_SEQ = 1024
STAGES = (2, 6)          # 2 stages: scanned runs of 12; 6: unrolled runs of 4
PROMPT_LENS = (32, 48, 64, 96, 128, 160, 200, 256, 320, 384, 448, 512)
NEW_TOKENS = 32
REFACTOR_TICK = 4        # all of the first MAX_BATCH requests are decoding
# Engine tokens against the float32 reference: the reference's logit of the
# token the engine picked may trail its top logit by at most this much.
# Random weights give unit-scale logits whose top two, over 151,936 ids, lie
# about 0.2 apart, so bf16 rounding alone can flip the argmax: on a v5e the
# worst trail was 0.0156 logit units.  Rounding to 8 bits moves such logits
# by tenths, and a token from a wrong position, cache row or layer trails by
# several units.
MARGIN_TOL = 0.1
# Four-stage pipeline against the single-device model, both bf16: the two
# differ only in summation order, and so in bf16 rounding, which 24 layers
# compound to a few percent of the logits (about 1% at 4 layers on the CPU).
# A stage, microbatch or cache slice routed wrongly gives uncorrelated
# logits: relative L2 near 1.4.
PIPE_MAX_ABS_TOL = 0.5
PIPE_REL_L2_TOL = 5e-2

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def require_tpu(n_chips: int):
    """Exit non-zero unless JAX's first device is a TPU and there are at
    least ``n_chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); there is no CPU fallback")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} chips, JAX found {len(devs)}")
    print(f"device: kind={devs[0].device_kind} count={len(devs)} "
          f"jax={jax.__version__}", flush=True)
    return devs


class CompileLog:
    """Backend compile durations, by program name, from JAX's monitoring
    events (a persistent-cache hit is recorded as its load time)."""

    def __init__(self):
        import jax
        self.events: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.events.append((kw.get("fun_name", "?"), float(duration)))

    def report(self, label: str, since: int) -> float:
        by_name: dict[str, list[float]] = {}
        for name, dt in self.events[since:]:
            by_name.setdefault(name, []).append(dt)
        for name, dts in sorted(by_name.items()):
            print(f"  compile[{label}] {name}: {len(dts)} programs, "
                  f"{sum(dts):.2f} s (max {max(dts):.2f} s)")
        return sum(dt for _, dt in self.events[since:])


def gb(n: int) -> str:
    return f"{n / 1e9:.3f} GB"


def mem(dev, key: str = "peak_bytes_in_use") -> int:
    return int(dev.memory_stats()[key])


# ---------------------------------------------------------------------------
# One chip: the served path
# ---------------------------------------------------------------------------

def make_requests(cfg, seed: int):
    import numpy as np
    from repro.serving.workload import Request
    rng = np.random.default_rng(seed)
    reqs = []
    for i, n in enumerate(PROMPT_LENS):
        r = Request(rid=i, arrival=0.0, prompt_len=n,
                    max_new_tokens=NEW_TOKENS)
        r.prompt_tokens = rng.integers(0, cfg.vocab_size, n, dtype=np.int64)
        reqs.append(r)
    return reqs


def check_tick_programs(eng, paged_kernel: bool) -> None:
    """Lower and compile every warmed granularity's decode tick again (the
    warm-up already built it, so this compiles nothing new) and print its
    memory analysis; with the paged kernel, the tick must hold the Mosaic
    call."""
    import jax.numpy as jnp
    from repro.serving.engine import balanced_boundaries
    B = eng.ecfg.max_batch
    tok = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    tables = eng._tables_dev()
    for n in STAGES:
        prog, _ = eng.executors.fused_decode(
            balanced_boundaries(eng.cfg.n_layers, n))
        t0 = time.perf_counter()
        compiled = prog.lower(eng.caches, tok, pos, tables).compile()
        dt = time.perf_counter() - t0
        ma = compiled.memory_analysis()
        print(f"  tick {n} stages: temp {gb(ma.temp_size_in_bytes)}, "
              f"arguments {gb(ma.argument_size_in_bytes)}, output "
              f"{gb(ma.output_size_in_bytes)}, aliased "
              f"{gb(ma.alias_size_in_bytes)}, code "
              f"{gb(ma.generated_code_size_in_bytes)} "
              f"(lower + compile again: {dt:.2f} s)")
        if paged_kernel:
            assert "tpu_custom_call" in compiled.as_text(), \
                f"paged {n}-stage tick holds no Mosaic kernel"
            print(f"  tick {n} stages: Mosaic table-walk kernel present")


def serve(label: str, cfg, params, reqs, log: CompileLog, dev, **kv):
    """Build an engine, serve ``reqs`` across one live refactor, check the
    run, and return {rid: emitted tokens}."""
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.serve import build_engine
    from repro.serving.engine import KVCacheConfig, balanced_boundaries

    print(f"[{label}] build: {cfg.name} bf16, max_batch {MAX_BATCH}, "
          f"max_seq {MAX_SEQ}, warm stages {STAGES}", flush=True)
    n0 = len(log.events)
    t0 = time.perf_counter()
    eng = build_engine(cfg, jnp.bfloat16, max_batch=MAX_BATCH,
                       max_seq=MAX_SEQ, stages=STAGES, params=params,
                       kv=KVCacheConfig(**kv))
    build_s = time.perf_counter() - t0
    compile_s = log.report(label, n0)
    print(f"  smoke reading: engine build with warm-up {build_s:.2f} s, "
          f"of which backend compile {compile_s:.2f} s")
    check_tick_programs(eng, kv.get("paged_kernel", False))

    for r in reqs:
        assert eng.submit(r, now=0.0), f"request {r.rid} refused"
    streams: dict[int, list[int]] = {}
    owner: dict[int, int | None] = {}
    tick_ms: dict[int, list[float]] = {}
    refactor = None
    now, tick = 0.0, 0
    n0 = len(log.events)
    t_serve = time.perf_counter()
    while len(eng.queue) or any(not s.done for s in eng.slots):
        assert tick < 20 * NEW_TOKENS, "serving did not finish"
        n_stages = len(eng.boundaries)
        t0 = time.perf_counter()
        rep = eng.step(now)      # ends in the host read of the tick's tokens
        dt = time.perf_counter() - t0
        if rep.decoded and not rep.admitted and not rep.prefill_tokens:
            tick_ms.setdefault(n_stages, []).append(dt * 1e3)
        # a slot's stream is complete in the step that finishes it; the
        # slot is reassigned no earlier than the next step
        for i, s in enumerate(eng.slots):
            if s.request is not None:
                owner[i] = s.request.rid
            if owner.get(i) is not None and s.generated:
                streams[owner[i]] = list(s.generated)
            if s.request is None:
                owner[i] = None
        if tick == REFACTOR_TICK:
            refactor = eng.refactor(balanced_boundaries(cfg.n_layers,
                                                        STAGES[1]))
        now += 0.05
        tick += 1
    serve_s = time.perf_counter() - t_serve
    print(f"  served {eng.stats.completed}/{len(reqs)} requests in {tick} "
          f"ticks, {serve_s:.2f} s")
    log.report(f"{label} serve", n0)

    assert eng.stats.completed == len(reqs), \
        f"completed {eng.stats.completed} != submitted {len(reqs)}"
    assert refactor is not None and refactor["inflight"] > 0, refactor
    print(f"  refactor {refactor['from']} -> {refactor['to']} with "
          f"{refactor['inflight']} in flight: compile_cache_hit="
          f"{refactor['compile_cache_hit']} new_traces="
          f"{refactor['new_traces']}")
    assert refactor["compile_cache_hit"] and refactor["new_traces"] == 0, \
        refactor
    print(f"  smoke reading: warm refactor stall "
          f"{refactor['t'] * 1e3:.3f} ms")
    for n, ms in sorted(tick_ms.items()):
        print(f"  smoke reading: mean decode tick at {n} stages "
              f"{float(np.mean(ms)):.3f} ms over {len(ms)} ticks "
              f"(engine.step up to the host read of the tokens)")
    for r in reqs:
        toks = np.asarray(streams.get(r.rid, []))
        assert len(toks) == NEW_TOKENS, (r.rid, len(toks))
        assert ((toks >= 0) & (toks < cfg.vocab_size)).all(), \
            f"request {r.rid}: token id outside the vocabulary"
    del eng
    gc.collect()
    print(f"  peak_bytes_in_use {gb(mem(dev))}", flush=True)
    return streams


def reference_margins(cfg, params, reqs, streams) -> float:
    """Teacher-force each stream through ``models.model.forward`` in float32
    at highest matmul precision; return the worst gap between the
    reference's top logit and its logit for the token the engine picked."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.model import forward

    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    L = max(r.prompt_len for r in reqs) + NEW_TOKENS
    L = -(-L // 128) * 128           # one padded length: one program

    @jax.jit
    def margins(p, toks, start, picks):
        logits = forward(cfg, p, {"tokens": toks})[0][0]
        rows = jax.lax.dynamic_slice_in_dim(logits, start, NEW_TOKENS, 0)
        picked = jnp.take_along_axis(rows, picks[:, None], axis=1)[:, 0]
        return rows.max(axis=1) - picked, rows.argmax(axis=1) == picks

    worst, same = 0.0, 0
    with jax.default_matmul_precision("highest"):
        for r in reqs:
            gen = np.asarray(streams[r.rid], np.int32)
            seq = np.concatenate([r.prompt_tokens, gen[:-1]])
            toks = np.zeros((1, L), np.int32)
            toks[0, :len(seq)] = seq
            m, eq = margins(p32, toks, np.int32(r.prompt_len - 1), gen)
            m = np.asarray(m)
            worst = max(worst, float(m.max()))
            same += int(np.asarray(eq).sum())
    n = len(reqs) * NEW_TOKENS
    print(f"  reference (float32, highest precision): worst margin "
          f"{worst:.4f} (tolerance {MARGIN_TOL}); engine token is the "
          f"reference argmax for {same}/{n} tokens")
    assert worst <= MARGIN_TOL, \
        f"engine token trails the reference top logit by {worst:.4f}"
    return worst


def one_chip(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.models.transformer import init_model

    dev = jax.devices()[0]
    log = CompileLog()
    params = init_model(jax.random.PRNGKey(seed), cfg, jnp.bfloat16)
    reqs = make_requests(cfg, seed)
    dense = serve("dense", cfg, params, reqs, log, dev)
    reference_margins(cfg, params, reqs, dense)
    gc.collect()
    reqs = make_requests(cfg, seed)
    paged = serve("paged", cfg, params, reqs, log, dev, paged=True,
                  paged_kernel=True)
    reference_margins(cfg, params, reqs, paged)
    same = sum(dense[r] == paged[r] for r in dense)
    agree = sum(a == b for r in dense for a, b in zip(dense[r], paged[r]))
    print(f"[paged vs dense] identical streams {same}/{len(dense)}, "
          f"agreeing tokens {agree}/{len(dense) * NEW_TOKENS}")
    print(f"peak_bytes_in_use {gb(mem(dev))}", flush=True)


# ---------------------------------------------------------------------------
# Four chips: the shard_map pipeline
# ---------------------------------------------------------------------------

def check_quarters(name: str, tree, devices) -> dict:
    """Every leaf is split over its leading (stage) axis into one quarter
    per chip; returns the bytes each chip holds."""
    import jax
    held = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(tree):
        shards = leaf.addressable_shards
        assert {s.device for s in shards} == set(devices), \
            f"{name}: a leaf is not spread over all {len(devices)} chips"
        starts = sorted(s.index[0].start or 0 for s in shards)
        quarter = leaf.shape[0] // len(devices)
        assert starts == [i * quarter for i in range(len(devices))], \
            f"{name}: stage slices {starts} are not one quarter per chip"
        for s in shards:
            assert s.data.shape[0] == quarter, (name, s.data.shape)
            held[s.device] += s.data.nbytes
    return held


def pipeline_phase(cfg, devices, seed: int, prompt: int = 256,
                   steps: int = 8) -> None:
    """Four-stage shard_map prefill + decode against the single-device
    model on ``devices[0]``, in bf16."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.configs.base import PipelinePlan, ShapeConfig
    from repro.models.model import decode_step, prefill
    from repro.models.transformer import init_model
    from repro.parallel.pipeline import (build_decode_step,
                                         build_prefill_step, stack_params)
    from repro.parallel.sharding import shardings

    bf16 = jnp.bfloat16
    B, S = MAX_BATCH, prompt + steps
    plan = PipelinePlan(stages=4, tensor=1, replica=1, microbatches=2)
    mesh = Mesh(np.asarray(devices).reshape(1, 4), ("data", "model"))
    print(f"[pipeline] {cfg.name} bf16, {plan}, batch {B}, prompt {prompt}, "
          f"{steps} decode steps", flush=True)
    pre, ps = build_prefill_step(cfg, plan, mesh,
                                 ShapeConfig("smoke_prefill", S, B, "prefill"),
                                 param_dtype=bf16, cache_dtype=bf16)
    dec, _ = build_decode_step(cfg, plan, mesh,
                               ShapeConfig("smoke_decode", S, B, "decode"),
                               param_dtype=bf16, cache_dtype=bf16)
    with jax.default_device(devices[0]):
        params = init_model(jax.random.PRNGKey(seed), cfg, bf16)
        stacked = jax.device_put(stack_params(cfg, plan, params),
                                 shardings(ps["mesh"], ps["pspecs"]))
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (B, S), dtype=np.int32)

    t0 = time.perf_counter()
    logits, caches = pre(stacked, {"tokens": tokens[:, :prompt]})
    pipe = [np.asarray(logits, np.float32)]
    print(f"  smoke reading: prefill compile + run "
          f"{time.perf_counter() - t0:.2f} s")
    held_p = check_quarters("stacked block params", stacked["stages"],
                            devices)
    held_c = check_quarters("KV caches", caches, devices)
    for d in devices:
        use = mem(d, "bytes_in_use")
        want = held_p[d] + held_c[d]
        print(f"  chip {d.id}: block params {gb(held_p[d])}, caches "
              f"{gb(held_c[d])}, bytes_in_use {gb(use)}")
        assert use >= want, f"chip {d.id} holds less than its quarter"
    t0 = time.perf_counter()
    for i in range(steps):
        tok = tokens[:, prompt + i:prompt + i + 1]
        logits, caches = dec(stacked, caches, tok, np.int32(prompt + i))
        pipe.append(np.asarray(logits, np.float32))
    print(f"  smoke reading: {steps} decode steps with compile "
          f"{time.perf_counter() - t0:.2f} s")

    with jax.default_device(devices[0]):
        ref_pre = jax.jit(lambda p, t: prefill(cfg, p, {"tokens": t}, S,
                                               cache_dtype=bf16))
        ref_dec = jax.jit(lambda p, t, c, pos: decode_step(cfg, p, t, c, pos),
                          donate_argnums=(2,))
        last, cache = ref_pre(params, tokens[:, :prompt])
        ref = [np.asarray(last, np.float32)]
        for i in range(steps):
            tok = tokens[:, prompt + i:prompt + i + 1]
            last, cache = ref_dec(params, tok, cache, np.int32(prompt + i))
            ref.append(np.asarray(last, np.float32))
    worst_abs, worst_rel, same = 0.0, 0.0, 0
    for a, b in zip(pipe, ref):
        worst_abs = max(worst_abs, float(np.abs(a - b).max()))
        worst_rel = max(worst_rel,
                        float(np.linalg.norm(a - b) / np.linalg.norm(b)))
        same += int((a.argmax(-1) == b.argmax(-1)).sum())
    print(f"  pipeline vs single device: max |dlogit| {worst_abs:.4f} "
          f"(tolerance {PIPE_MAX_ABS_TOL}), relative L2 {worst_rel:.5f} "
          f"(tolerance {PIPE_REL_L2_TOL}), argmax agrees "
          f"{same}/{B * (steps + 1)}")
    assert worst_abs <= PIPE_MAX_ABS_TOL and worst_rel <= PIPE_REL_L2_TOL
    for d in devices:
        print(f"  chip {d.id}: peak_bytes_in_use {gb(mem(d))}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve on one chip; 4: only the four-stage "
                         "shard_map pipeline phase")
    args = ap.parse_args()

    devs = require_tpu(args.chips)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs.base import get_arch
    from repro.launch.serve import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = get_arch(ARCH).config
    if args.chips == 4:
        pipeline_phase(cfg, devs[:4], SEED)
    else:
        one_chip(cfg, SEED)
    import jax
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
