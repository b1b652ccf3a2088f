"""The benchmark harness: one cell, one run.

A cell (``BENCHMARK.json`` ``workloads`` entry) names a configuration and a
traffic mix; both are data files found by name
(``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``), and each
metric is a reader of its own (``bench/metrics/<metric>.py``, ``read(run)``).
A configuration names its architecture module beside it
(``bench/configs/<architecture>.py``), which makes the weights from the seed
and holds the plain float32 reference.

A run: weights on the device from the seed; the engine built through
``repro.launch.serve.build_engine`` with every granularity the cell can use
precompiled; every prompt bucket of the traffic warmed through the served
path; then the window: open-loop arrivals submitted to
``FlexPipeEngine.submit`` when due, ``FlexPipeEngine.step`` on the wall
clock, and, where the traffic has a controller,
``FlexPipeController.control_step`` every ``control_interval`` with
``engine.refactor`` to the granularity it picks (``engine.run`` with a real
clock).  After the window the served tokens of a sample of finished requests
are compared with the reference.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import tempfile
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Finding a cell's files by name
# ---------------------------------------------------------------------------

def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_file(directory: Path, name: str, exts=(".json",)) -> Path:
    hits = [directory / f"{name}{e}" for e in exts
            if (directory / f"{name}{e}").is_file()]
    if len(hits) != 1:
        raise FileNotFoundError(
            f"expected one file for {name!r} in {directory} with an "
            f"extension of {exts}, found {[h.name for h in hits]}")
    return hits[0]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file
    traffic: dict         # the traffic file
    arch: object          # architecture module (weights, reference)
    end_to_end: list      # metric entries of BENCHMARK.json for this cell
    per_layer: list
    readers: dict         # metric name -> module with read(run)


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bm = json.loads((root / "BENCHMARK.json").read_text())
    ws = [w for w in bm["workloads"] if w["name"] == workload]
    if len(ws) != 1:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = ws[0]
    cs = [c for c in bm["configs"] if c["name"] == w["config"]]
    if len(cs) != 1:
        raise KeyError(f"no configuration {w['config']!r} in BENCHMARK.json")
    config = json.loads((root / cs[0]["file"]).read_text())
    bench = root / "bench"
    traffic = json.loads(find_file(bench / "traffic", w["traffic"])
                         .read_text())
    arch = _load_module(find_file(bench / "configs", config["architecture"],
                                  (".py",)))
    e2e = [m for m in bm["end_to_end"] if _applies(m, workload, set())]
    names = {m["name"] for m in e2e}
    pl = [m for m in bm["per_layer"] if _applies(m, workload, names)]
    readers = {m["name"]: _load_module(find_file(bench / "metrics",
                                                 m["name"], (".py",)))
               for m in e2e + pl}
    return Cell(workload, int(w["chips"]), config, traffic, arch, e2e, pl,
                readers)


# ---------------------------------------------------------------------------
# What a run records
# ---------------------------------------------------------------------------

@dataclass
class ReqRecord:
    rid: int
    due: float                   # window clock; fill requests are due < 0
    prompt: np.ndarray
    max_new_tokens: int
    request: object = None       # the engine's Request
    stamps: list = field(default_factory=list)   # window clock, per token
    tokens: list = field(default_factory=list)
    finished: float = math.nan


@dataclass
class StepRecord:
    t0: float
    t1: float
    decoded: int                 # tokens from the decode tick
    ctx_sum: int                 # attended rows over the decoded slots
    live_blocks: int             # paged blocks those rows occupy
    prompt_tokens: int           # prompt tokens of requests admitted
    admitted: int


@dataclass
class Run:
    """Everything a metric reader may read."""
    cell: Cell
    model: dict                  # sizes of the configuration (arch.dims)
    serve: dict
    seconds: float
    setup_s: float
    requests: list
    steps: list
    refactors: list              # engine.refactor() reports in the window
    controller: bool
    trace: object = None         # xtrace.Summary of a traced run
    peaks: dict = None           # bench.peaks entry of the device

    def in_window(self, t: float) -> bool:
        return 0.0 <= t < self.seconds


# ---------------------------------------------------------------------------
# Running
# ---------------------------------------------------------------------------

class Spans:
    """Host spans: in the profiler's trace when tracing, else nothing."""

    def __init__(self, on: bool):
        self.on = on
        if on:
            from jax.profiler import TraceAnnotation
            self._ann = TraceAnnotation

    def __call__(self, name: str):
        return self._ann(name) if self.on else nullcontext()


class CompileLog:
    """Backend compiles and persistent-cache loads, from JAX's monitoring
    events: count and seconds."""

    def __init__(self):
        import jax
        self.n = 0
        self.s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.n += 1
            self.s += float(duration)


class GcLog:
    """Passes of Python's garbage collector while installed: count, seconds
    and the longest."""

    def __init__(self):
        self.n, self.s, self.longest = 0, 0.0, 0.0
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.n += 1
            self.s += d
            self.longest = max(self.longest, d)
            self._t = None

    def close(self) -> None:
        gc.callbacks.remove(self._on)


def bytes_in_use(dev) -> int:
    stats = dev.memory_stats() if dev.platform == "tpu" else None
    return int(stats["bytes_in_use"]) if stats else 0


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r}); "
                     "the benchmark does not fall back to another device")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs


def enable_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program, however fast it compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def prompt_buckets(traffic: dict, max_seq: int) -> list:
    """The engine's pow2 prompt buckets (``prefill_bucket``) that prompts of
    this mix fall into."""
    lo, hi = traffic["prompt"]["min"], traffic["prompt"]["max"]
    out, b = [], 16
    while b < hi:
        b *= 2
        if b >= lo:
            out.append(min(b, max_seq))
    if lo <= 16:
        out.insert(0, 16)
    return sorted(set(out))


class Server:
    """The served path on the wall clock, with the harness's stamps."""

    def __init__(self, eng, controller, interval: float, spans: Spans,
                 clock0: float):
        self.eng = eng
        self.controller = controller
        self.interval = interval
        self.spans = spans
        self.clock0 = clock0
        self.owner: list = [None] * eng.ecfg.max_batch
        self.seen: list = [0] * eng.ecfg.max_batch
        self.records: dict = {}
        self.steps: list = []
        self.refactors: list = []
        self.lateness: list = []

    def now(self) -> float:
        return time.perf_counter() - self.clock0

    def submit(self, rec: ReqRecord, now: float) -> bool:
        from repro.serving.workload import Request
        r = Request(rid=rec.rid, arrival=rec.due,
                    prompt_len=len(rec.prompt),
                    max_new_tokens=rec.max_new_tokens)
        r.prompt_tokens = rec.prompt
        rec.request = r
        self.records[rec.rid] = rec
        self.lateness.append(now - rec.due)
        with self.spans("bench.submit"):
            ok = bool(self.eng.submit(r, now=rec.due))
        if self.controller is not None:
            self.controller.on_request(rec.due)
        return ok

    def step(self) -> None:
        eng = self.eng
        t0 = self.now()
        with self.spans("bench.step"):
            eng.step(t0)
        t1 = self.now()
        with self.spans("bench.stamp"):
            self._stamp(t0, t1)

    def _stamp(self, t0: float, t1: float) -> None:
        from bench import flops
        bs = self.eng.ecfg.block_size
        decoded = ctx = blocks = ptoks = admitted = 0
        for i, s in enumerate(self.eng.slots):
            req = s.request
            new_owner = req is not None and (
                self.owner[i] is None or req.rid != self.owner[i])
            if new_owner:
                self.owner[i] = req.rid
                self.seen[i] = 0
                admitted += 1
                ptoks += req.prompt_len
            rid = self.owner[i]
            if rid is None:
                continue
            rec = self.records[rid]
            fresh = s.generated[self.seen[i]:]
            if fresh:
                rec.tokens.extend(int(t) for t in fresh)
                rec.stamps.extend([t1] * len(fresh))
                self.seen[i] = len(s.generated)
                if len(fresh) - (1 if new_owner else 0) > 0:
                    decoded += 1
                    ctx += s.pos
                    blocks += flops.blocks(s.pos, bs)
            if req is None:                      # finished in this step
                rec.finished = t1
                self.owner[i] = None
        self.steps.append(StepRecord(t0, t1, decoded, ctx, blocks, ptoks,
                                     admitted))

    def busy(self) -> bool:
        return bool(len(self.eng.queue)) or any(
            not s.done for s in self.eng.slots)

    def control(self, now: float) -> None:
        from repro.serving.engine import balanced_boundaries
        with self.spans("bench.control"):
            d, _ = self.controller.control_step(now, len(self.eng.queue))
        L = self.eng.cfg.n_layers
        if d.changed and d.target.stages <= L:
            nb = balanced_boundaries(L, d.target.stages)
            if nb != self.eng.boundaries:
                with self.spans("bench.refactor"):
                    self.refactors.append(self.eng.refactor(nb))

    def window(self, arrivals: list, seconds: float) -> None:
        """Serve ``arrivals`` (window clock, sorted) until ``seconds``."""
        i, last_ctl = 0, 0.0
        n = len(arrivals)
        while True:
            now = self.now()
            if now >= seconds:
                break
            while i < n and arrivals[i].due <= now:
                self.submit(arrivals[i], now)
                i += 1
            if self.busy():
                self.step()
            else:
                nxt = arrivals[i].due if i < n else seconds
                with self.spans("bench.wait"):
                    time.sleep(max(0.0, min(nxt, seconds) - self.now()))
            if self.controller is not None and \
                    now - last_ctl >= self.interval:
                last_ctl = now
                self.control(now)


def _profiles(traffic: dict):
    from repro.core.granularity import GranularityProfile
    ctl = traffic.get("controller")
    if not ctl:
        return []
    return [GranularityProfile(**p) for p in ctl["profiles"]]


def _pctl(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else \
        math.nan


def _log(*a) -> None:
    print(*a, flush=True)


@dataclass
class Prepared:
    """A built and warmed engine with its weights, ready to serve."""
    cell: Cell
    cfg: object                  # the program's ModelConfig
    model: dict
    params: dict
    eng: object
    profiles: list
    split: dict                  # set-up seconds by part
    n_programs: int
    log: CompileLog
    dev: object
    devs: list


def prepare(cell: Cell, seed: int, root: Path = ROOT,
            check_chips: bool = True, log: CompileLog | None = None
            ) -> Prepared:
    """Weights from the seed, the engine with every granularity the cell
    can use precompiled, and every prompt bucket of the traffic warmed
    through the served path."""
    import jax
    import jax.numpy as jnp
    from bench.traffic_gen import seed_key_int
    from repro.configs.base import ModelConfig
    from repro.launch.serve import build_engine
    from repro.serving.engine import KVCacheConfig, balanced_boundaries

    devs = require_chips(cell.chips) if check_chips else jax.devices()
    enable_compile_cache(root)
    log = log or CompileLog()
    c, tr, sv = cell.config, cell.traffic, cell.config["serve"]
    m = cell.arch.dims(c)
    dtype = jnp.dtype(sv["dtype"])
    max_batch, max_seq = int(sv["max_batch"]), int(sv["max_seq"])
    split = {}

    # weights, made on the device from the seed in one jitted call
    t0 = time.perf_counter()
    params = cell.arch.make_params(
        c, jax.random.PRNGKey(seed_key_int(seed)), dtype)
    jax.block_until_ready(params)
    split["weights"] = time.perf_counter() - t0

    t0, n0, s0 = time.perf_counter(), log.n, log.s
    profiles = _profiles(tr)
    stages = tuple(p.stages for p in profiles) or (int(sv["stages"]),)
    cfg = ModelConfig(**cell.arch.program_config(c))
    eng = build_engine(
        cfg, dtype, max_batch=max_batch, max_seq=max_seq, stages=stages,
        params=params,
        control_interval=float((tr.get("controller") or {}).get(
            "control_interval", 1.0)),
        kv=KVCacheConfig(paged=bool(sv["paged"]),
                         block_size=int(sv["block_size"]),
                         paged_kernel=bool(sv["paged_kernel"])))
    # every prompt bucket of the mix at every granularity, through the
    # served path: one request a bucket, ending at its first token
    srv = Server(eng, None, 0.0, Spans(False), time.perf_counter())
    rid = -1
    for n_st in stages:
        eng.refactor(balanced_boundaries(cfg.n_layers, n_st))
        for b in prompt_buckets(tr, max_seq):
            plen = min(b, max_seq - 2)
            srv.submit(ReqRecord(rid, 0.0, np.arange(plen) % m["V"], 1),
                       srv.now())
            rid -= 1
        while srv.busy():
            srv.step()
    eng.refactor(balanced_boundaries(cfg.n_layers, stages[0]))
    jax.block_until_ready(eng.caches)
    split["programs"] = log.s - s0
    split["warm"] = time.perf_counter() - t0 - split["programs"]
    return Prepared(cell, cfg, m, params, eng, profiles, split, log.n - n0,
                    log, devs[0], devs)


def serve(p: Prepared, arrivals: list, fill: list, seconds: float,
          controller, spans: Spans, on_open=None) -> Server:
    """Admit ``fill`` before the window, then serve ``arrivals`` open-loop
    for ``seconds``.  ``on_open`` runs just before the window opens."""
    import jax
    from bench import xtrace
    eng = p.eng
    srv = Server(eng, controller, eng.ecfg.control_interval, spans, 0.0)
    t0 = time.perf_counter()
    srv.clock0 = t0
    for a in fill:
        a.due = 0.0
        srv.submit(a, 0.0)
    while len(eng.queue):
        srv.step()
    p.split["fill"] = time.perf_counter() - t0
    for a in fill:                      # fill requests are not of the window
        a.due = -math.inf
    if on_open is not None:
        on_open()
    # the window's clock starts now: what the fill stamped lies before 0
    t_open = time.perf_counter()
    shift = t_open - srv.clock0
    for r in srv.records.values():
        r.stamps = [t - shift for t in r.stamps]
        r.finished -= shift
    srv.clock0 = t_open
    with spans(xtrace.WINDOW_SPAN):
        srv.window(arrivals, seconds)
    jax.block_until_ready(eng.caches)
    return srv


def make_traffic(p: Prepared, traffic: dict, seconds: float, seed: int):
    """(fill requests, window arrivals) of a traffic mix."""
    from bench.traffic_gen import schedule
    n_fill = p.eng.ecfg.max_batch if traffic.get("fill_batch") else 0
    reqs = [ReqRecord(a.rid, a.due, a.prompt, a.max_new_tokens)
            for a in schedule(traffic, seconds, seed, p.model["V"], n_fill)]
    return reqs[:n_fill], reqs[n_fill:]


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, check_chips: bool = True,
             t_start: float | None = None) -> dict:
    """One run of one cell; returns the result line's object."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(workload, root)
    import jax
    from bench import peaks, xtrace
    from repro.core.controller import FlexPipeController

    p = prepare(cell, seed, root, check_chips)
    log, dev, tr = p.log, p.dev, cell.traffic
    t0 = time.perf_counter()
    fill, arrivals = make_traffic(p, tr, seconds, seed)
    p.split["traffic"] = time.perf_counter() - t0
    controller = (FlexPipeController(p.cfg, p.profiles) if p.profiles
                  else None)
    spans = Spans(trace)
    tracedir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    marks = {}

    def on_open():
        marks["compiles"] = log.n
        marks["bytes_open"] = bytes_in_use(dev)
        marks["gc"] = GcLog()
        if trace:
            # host spans from the harness's annotations only: tracing every
            # Python call would slow the host loop being measured
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tracedir, profiler_options=opts)
        marks["setup_s"] = time.perf_counter() - t_start

    srv = serve(p, arrivals, fill, seconds, controller, spans, on_open)
    gcl = marks["gc"]
    gcl.close()
    if trace:
        jax.profiler.stop_trace()
    window_compiles = log.n - marks["compiles"]
    setup_s, split = marks["setup_s"], p.split
    peak = int(dev.memory_stats()["peak_bytes_in_use"]) \
        if dev.platform == "tpu" else 0
    bytes_close = bytes_in_use(dev)
    n_programs = p.n_programs
    m = p.model

    # lines before the result
    recs = list(srv.records.values())
    due_in = [r for r in recs if 0.0 <= r.due < seconds]
    _log(f"setup: {setup_s:.3f} s = weights {split['weights']:.3f} + "
         f"program loads {split['programs']:.3f} ({n_programs} programs) + "
         f"warm executions {split['warm']:.3f} + traffic "
         f"{split['traffic']:.3f} + slot fill {split['fill']:.3f} + "
         f"other {setup_s - sum(split.values()):.3f}")
    late = np.asarray(srv.lateness[len(fill):] or [0.0]) * 1e3
    _log(f"generator lateness: p50 {np.median(late):.3f} ms, p95 "
         f"{np.percentile(late, 95):.3f} ms, max {late.max():.3f} ms over "
         f"{len(late)} arrivals")
    _log(f"window: compiles {window_compiles}, refactors "
         f"{len(srv.refactors)} {[r['to'] for r in srv.refactors]}, steps "
         f"{len(srv.steps)}, decode ticks "
         f"{sum(1 for s in srv.steps if s.decoded)}, requests due "
         f"{len(due_in)}, finished "
         f"{sum(1 for r in recs if not math.isnan(r.finished))}, longest "
         f"step {1e3 * max((s.t1 - s.t0 for s in srv.steps), default=0):.1f}"
         f" ms, garbage collections {gcl.n} ({1e3 * gcl.s:.1f} ms, longest "
         f"{1e3 * gcl.longest:.1f} ms)")
    _log(f"memory: peak {peak} bytes (set-up and window); in use "
         f"{marks['bytes_open']} at the window's opening, {bytes_close} at "
         f"its close")
    lim = tr.get("limits", {})
    if lim:
        met = 0
        for r in due_in:
            st = [t for t in r.stamps if t < seconds]
            if not st or st[0] - r.due > lim["ttft_s"]:
                continue
            tpot = (st[-1] - st[0]) / (len(st) - 1) if len(st) > 1 else 0.0
            met += tpot <= lim["tpot_s"]
        ttft_all = [r.stamps[0] - r.due for r in due_in
                    if r.stamps and r.stamps[0] < seconds]
        _log(f"limits (ttft {lim['ttft_s']} s, time per output token "
             f"{lim['tpot_s']} s): met by {met}/{len(due_in)} requests due "
             f"in the window ({100.0 * met / max(len(due_in), 1):.1f}%); "
             f"ttft p50 {1e3 * _pctl(ttft_all, 50):.1f} ms, p95 "
             f"{1e3 * _pctl(ttft_all, 95):.1f} ms over {len(ttft_all)}")

    run = Run(cell=cell, model=m, serve=cell.config["serve"], seconds=seconds,
              setup_s=setup_s,
              requests=recs, steps=srv.steps, refactors=srv.refactors,
              controller=controller is not None,
              peaks=peaks.peaks_for(dev.device_kind)
              if dev.platform == "tpu" else None)
    finished = [r for r in recs if not math.isnan(r.finished)]
    params = p.params
    del srv, p, controller
    gc.collect()

    checks = check_outputs(cell, params, finished, seed)
    del params
    gc.collect()

    if trace:
        import shutil
        try:
            run.trace = xtrace.reduce(xtrace.load_xspace(tracedir))
        finally:
            shutil.rmtree(tracedir, ignore_errors=True)
        _log(f"trace: window {run.trace.window_s:.3f} s, busy "
             f"{run.trace.busy_s:.3f} s, tick programs "
             f"{len(run.trace.modules_named('jit_tick'))}, prefill programs "
             f"{len(run.trace.modules_named('jit_prefill'))}")
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for md in wanted:
        v = cell.readers[md["name"]].read(run)
        if v is not None:
            metrics[md["name"]] = {"value": float(v), "unit": md["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": is_correct(checks),
           "attempted": len(fill) + len(due_in),
           "failed": sum(1 for r in recs
                         if r.request is not None and (
                             getattr(r.request, "failed", False)
                             or getattr(r.request, "rejected", False))),
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = checks
    return out


# ---------------------------------------------------------------------------
# Correctness: served tokens against the reference
# ---------------------------------------------------------------------------

def sample_requests(finished: list, seed: int, want_tokens: int) -> list:
    """The finished request with the most served tokens, then others drawn
    from the seed until the sample holds ``want_tokens`` served tokens."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r.tokens), r.rid))
    rng = np.random.default_rng((seed % 2**64, 2))
    rest = [order[i] for i in rng.permutation(len(order) - 1) + 1]
    out, n = [order[0]], len(order[0].tokens)
    for r in rest:
        if n >= want_tokens:
            break
        out.append(r)
        n += len(r.tokens)
    return out


def gap_readings(arch, config: dict, params, rec: ReqRecord,
                 control: bool = False) -> dict:
    """Teacher-forced through the prompt and the served tokens, for each
    served token: ``served``, how far the reference's logit of it lies below
    the reference's best logit.  With ``control`` also ``control``: the gap
    of the token that the fp8 control puts first at that position, the
    control standing in for the program."""
    S, toks = len(rec.prompt), np.asarray(rec.tokens, np.int64)
    seq = np.concatenate([rec.prompt, toks[:-1]])
    picks = np.zeros((len(seq), 2 if control else 1), np.int32)
    picks[S - 1:, 0] = toks
    if control:
        _, arg, _ = arch.logit_stats(config, params, seq, picks[:, :1],
                                     fp8=True)
        picks[S - 1:, 1] = arg[S - 1:]
    best, _, got = arch.logit_stats(config, params, seq, picks)
    gaps = best[:, None] - got
    out = {"served": gaps[S - 1:, 0]}
    if control:
        out["control"] = gaps[S - 1:, 1]
    return out


def readings(cell: Cell, params, finished: list, seed: int,
             control: bool = False) -> dict:
    """``gap_readings`` over the sample of finished requests that a run
    compares, concatenated; ``requests`` is the sample."""
    c = cell.config
    sample = sample_requests(finished, seed, int(c["correct"]["sample_tokens"]))
    t0 = time.perf_counter()
    parts = [gap_readings(cell.arch, c, params, r, control) for r in sample]
    keys = ("served",) + (("control",) if control else ())
    out = {k: np.concatenate([q[k] for q in parts]) if parts else np.zeros(0)
           for k in keys}
    _log(f"reference: {len(sample)} requests, {len(out['served'])} served "
         f"tokens (longest {len(sample[0].tokens) if sample else 0})"
         f"{', with the control' if control else ''}, "
         f"{time.perf_counter() - t0:.3f} s")
    out["requests"] = sample
    return out


def logit_numbers(gaps: np.ndarray) -> dict:
    """The numbers a configuration's ``correct`` group may hold a run to,
    from the per-token gaps of the tokens judged."""
    if not len(gaps):
        return {"max_logit_gap": math.inf, "mean_logit_gap": math.inf}
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean())}


def judge(cell: Cell, finished: list, r: dict, key: str = "served") -> dict:
    """The numbers compared, each with its limit: the logit-gap numbers that
    the configuration's ``correct`` group names, of the tokens under ``key``
    of ``readings`` (the served tokens, or the control's standing in for
    them), and the served streams' own checks.  A number whose limit is
    null has no limit measured yet, and nothing passes it."""
    c = cell.config
    V = cell.arch.dims(c)["V"]
    values = logit_numbers(r[key])
    checks = {name: {"value": values[name],
                     "limit": -1.0 if lim is None else float(lim)}
              for name, lim in c["correct"].items() if name in values}
    checks.update({
        "no_request_compared": {"value": int(not r["requests"]), "limit": 0},
        "short_streams": {"value": sum(
            1 for q in finished if len(q.tokens) != q.max_new_tokens),
            "limit": 0},
        "token_ids_out_of_range": {"value": sum(
            1 for q in finished for t in q.tokens if not 0 <= t < V),
            "limit": 0},
    })
    return checks


def is_correct(checks: dict) -> bool:
    return all(ch["value"] <= ch["limit"] for ch in checks.values())


def check_outputs(cell: Cell, params, finished: list, seed: int,
                  control: bool = False) -> dict:
    """``judge`` of the served tokens against the reference; with
    ``control``, of the fp8 control's tokens in their place."""
    r = readings(cell, params, finished, seed, control)
    return judge(cell, finished, r, "control" if control else "served")
