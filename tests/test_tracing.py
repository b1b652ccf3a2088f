"""Host spans of the serving engine (serving/tracing.py): every phase of
``engine.step`` is a named ``TraceAnnotation`` nested in ``engine.step``,
a profiler trace holds them, and the tokens served do not change."""
from contextlib import nullcontext
from pathlib import Path

import jax
import pytest

from repro.configs.base import get_arch
from repro.models.transformer import init_model
from repro.serving import tracing
from repro.serving.engine import (EngineConfig, FlexPipeEngine,
                                  KVCacheConfig, PrefillConfig)
from repro.serving.workload import Request

CFG = get_arch("qwen1.5-0.5b").smoke_config
PARAMS = init_model(jax.random.PRNGKey(0), CFG)

DECODE = {"engine.step", "engine.faults", "engine.admit", "engine.sync",
          "engine.decode.prepare", "engine.decode.dispatch",
          "engine.decode.bookkeep"}


class Recorder:
    """Stands in for ``TraceAnnotation``: records each span's name, its
    arguments and the spans open around it."""

    def __init__(self):
        self.spans = []
        self.open = []

    def __call__(self, name, **args):
        rec = self

        class Span:
            def __enter__(self):
                rec.spans.append((name, args, tuple(rec.open)))
                rec.open.append(name)

            def __exit__(self, *exc):
                rec.open.pop()

        return Span()


@pytest.fixture
def recorder(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(tracing, "TraceAnnotation", rec)
    return rec


def test_span_on_is_a_trace_annotation():
    sp = tracing.span("engine.prefill", rid=3, bucket=64)
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    with sp:
        pass


def _serve(ecfg, steps: int = 12):
    eng = FlexPipeEngine(CFG, PARAMS, [0, 2], ecfg)
    for i in range(3):
        eng.submit(Request(rid=i, arrival=0.0, prompt_len=12 + 9 * i,
                           max_new_tokens=6), now=0.0)
    for t in range(steps):
        if t == 4:
            eng.refactor([0, 1, 2, 3])
        eng.step(0.05 * t)
    return [r.generated for r in eng.slots], eng


def test_profiler_trace_holds_engine_spans(tmp_path):
    """Inside ``jax.profiler.trace`` the engine's phases are host events on
    the trace's clock, with no switch to turn them on."""
    ecfg = EngineConfig(max_batch=2, max_seq=64)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        _serve(ecfg, steps=6)
    path, = Path(tmp_path).rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    names = {ev.name for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert DECODE | {"engine.prefill", "engine.refactor"} <= names


@pytest.mark.parametrize("mode", ["dense", "paged", "chunked"])
def test_engine_step_phases_are_spans(recorder, monkeypatch, mode):
    ecfg = EngineConfig(
        max_batch=2, max_seq=64,
        kv=KVCacheConfig(paged=mode == "paged", block_size=8),
        prefill=PrefillConfig(chunk=16 if mode == "chunked" else 0))
    with monkeypatch.context() as mp:       # the engine with no spans
        mp.setattr(tracing, "TraceAnnotation",
                   lambda name, **args: nullcontext())
        off, _ = _serve(ecfg)
    on, _ = _serve(ecfg)
    assert on == off                     # spans change nothing served
    names = {n for n, _, _ in recorder.spans}
    prefill = "engine.prefill_chunk" if mode == "chunked" else \
        "engine.prefill"
    assert names == DECODE | {prefill, "engine.refactor"}
    for name, args, outer in recorder.spans:
        if name == "engine.refactor":
            continue                     # called outside a step here
        assert name == "engine.step" or outer[0] == "engine.step", name
        if name == "engine.prefill":
            assert set(args) == {"rid", "bucket"}
        if name == "engine.prefill_chunk":
            assert set(args) == {"rid"}
        if name == "engine.sync":
            assert outer[-1] in ("engine.step", prefill)
    # one span per phase, not per slot: a decode tick opens each once
    ticks = sum(1 for n, _, _ in recorder.spans
                if n == "engine.decode.dispatch")
    assert sum(1 for n, _, _ in recorder.spans
               if n == "engine.decode.bookkeep") == ticks
