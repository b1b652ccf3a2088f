"""The tick's device time by named scope and the idle by engine span
(bench/scopes.py), on synthetic traces and on the fused tick compiled for
the CPU at smoke size."""
import jax
import pytest

from bench import scopes, xtrace
from bench.xtrace import Interval, RawTrace
from repro.configs.base import get_arch
from repro.models.transformer import init_model
from repro.serving.engine import (EngineConfig, FlexPipeEngine,
                                  KVCacheConfig, balanced_boundaries)

CFG = get_arch("qwen1.5-0.5b").smoke_config
PARAMS = init_model(jax.random.PRNGKey(0), CFG)


def _engine(paged: bool):
    kv = KVCacheConfig(paged=paged, block_size=8, paged_kernel=paged)
    return FlexPipeEngine(CFG, PARAMS, balanced_boundaries(CFG.n_layers, 2),
                          EngineConfig(max_batch=4, max_seq=64, kv=kv))


@pytest.mark.parametrize("op_name,want", [
    ("jit(tick)/attention/kv_write/scatter", "kv_write"),
    ("jit(tick)/attention/bsd,dhk->bshk/dot_general", "attention"),
    ("jit(tick)/mlp/jit(_where)/select_n", "mlp"),
    ("caches[0]['mixer']['k'];jit(tick)/head/argmax", "head"),
    ("jit(tick)/embed/gather;jit(tick)/mlp/add", "embed"),
    ("caches[0]['mixer']['k']", None),
    ("", None)])
def test_scope_is_the_innermost_known(op_name, want):
    assert scopes.scope_of(op_name) == want


HLO = """HloModule jit_tick, entry_computation_layout={()}

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %exp.1 = f32[4]{0} exponential(%p), metadata={op_name="jit(tick)/mlp/exp"}
}

ENTRY %main.3 (c: f32[4]) -> f32[4] {
  %c = f32[4]{0} parameter(0), metadata={op_name="caches[0]['mixer']['k']"}
  %copy.5 = f32[4]{0} copy(%c), metadata={op_name="caches[0]['mixer']['k']"}
  %fusion.2 = f32[4]{0} fusion(%copy.5), kind=kLoop, calls=%fused_computation.1
  %dot.7 = f32[4]{0} add(%fusion.2, %copy.5), metadata={op_name="jit(tick)/attention/kv_write/add" stack_frame_id=3}
  ROOT %copy.9 = f32[4]{0} copy(%dot.7)
}
"""


def test_instructions_read_op_names_and_fusion_roots():
    ins = scopes.instructions(HLO)
    assert ins["copy.5"] == ("%copy.5 = f32[4]{0} copy(%c)",
                             "caches[0]['mixer']['k']")
    assert ins["dot.7"][1] == "jit(tick)/attention/kv_write/add"
    # a fusion without metadata takes its computation's root's op_name
    assert ins["fusion.2"][1] == "jit(tick)/mlp/exp"
    assert ins["copy.9"][1] == ""
    smap = scopes.ScopeMap([HLO])
    assert [smap.scope(f"%{n} = f32[4]{{0}} x") for n in
            ("copy.5", "fusion.2", "dot.7", "copy.9", "nowhere.1")] == \
        ["unscoped", "mlp", "kv_write", "unscoped", "unscoped"]


def test_repeated_names_go_by_the_closer_program():
    other = HLO.replace('%dot.7 = f32[4]{0} add(', '%dot.7 = f32[8]{0} add(') \
        .replace("attention/kv_write/add", "head/add")
    smap = scopes.ScopeMap([HLO, other])
    assert smap.scope("%dot.7 = f32[4]{0} add(%fusion.2, %copy.5)") \
        == "kv_write"
    assert smap.scope("%dot.7 = f32[8]{0} add(%fusion.2, %copy.5)") == "head"


def test_scope_seconds_counts_tick_ops_once():
    """Ops inside a ``jit_tick`` execution of the window land in exactly one
    bucket; ops of another program or outside the window are left out."""
    smap = scopes.ScopeMap([HLO])
    ev = {"a": "%dot.7 = f32[4]{0} add(%fusion.2, %copy.5)",
          "m": "%fusion.2 = f32[4]{0} fusion(%copy.5)",
          "u": "%copy.5 = f32[4]{0} copy(%c)"}
    ops = {"tpu0": [Interval(ev["u"], 1.0, 1.3), Interval(ev["m"], 1.3, 1.4),
                    Interval(ev["a"], 1.4, 1.9), Interval(ev["a"], 2.1, 2.2),
                    Interval(ev["m"], 3.0, 3.5), Interval(ev["a"], 0.1, 0.2)]}
    modules = {"tpu0": [Interval("jit_tick", 1.0, 2.0),
                        Interval("jit_prefill", 2.05, 2.3),
                        Interval("jit_tick", 3.0, 3.6),
                        Interval("jit_tick", 0.0, 0.5)]}
    got = scopes.scope_seconds(ops, modules, (0.9, 4.0), smap)
    assert got == pytest.approx({"embed": 0.0, "attention": 0.0,
                                 "kv_write": 0.5, "mlp": 0.6, "head": 0.0,
                                 "unscoped": 0.3})
    n = scopes.ticks_in(modules, (0.9, 4.0))
    assert n == 2
    assert scopes.tick_attention_ms(got, n) == pytest.approx(250.0)
    assert sum(scopes.per_tick_ms(got, n).values()) == pytest.approx(700.0)
    assert scopes.scope_line(got, n).endswith("sum 700.000")
    assert scopes.tick_attention_ms(got, 0) is None


@pytest.mark.parametrize("paged", [False, True])
def test_compiled_tick_splits_into_scopes(paged):
    """Events named by the compiled tick's own entry instructions, as a
    TPU's trace names them: every op is counted in one scope or in
    ``unscoped``, the buckets sum to the tick's op time, and the dots,
    the cache writes and the head each land in their scope."""
    eng = _engine(paged)
    text, = scopes.tick_texts(eng, [eng.boundaries])
    smap = scopes.ScopeMap([text])
    ins = scopes.instructions(text)
    entry = text[text.index("\nENTRY"):]
    names = [n for n in ins if f"%{n} = " in entry
             and " parameter(" not in ins[n][0]]
    evs = [Interval(ins[n][0], 1.0 + i * 1e-3, 1.0 + (i + 1) * 1e-3)
           for i, n in enumerate(names)]
    got = scopes.scope_seconds(
        {"tpu0": evs}, {"tpu0": [Interval("jit_tick", 1.0, 2.0)]},
        (0.0, 3.0), smap)
    assert sum(got.values()) == pytest.approx(len(names) * 1e-3)
    by = {n: smap.scope(ins[n][0]) for n in names}
    for n, (line, _) in ins.items():
        if n in by and (" dot(" in line or "custom_call_target" in line):
            assert by[n] in ("attention", "mlp", "head"), line
    assert {"embed", "attention", "kv_write", "mlp", "head"} <= set(
        by.values())


def test_engine_spans_take_bench_step_idle():
    """A gap inside ``engine.*`` spans nested in ``bench.step`` goes to the
    innermost engine span; a gap outside every engine span stays with
    ``bench.step``."""
    raw = RawTrace(
        ops={"tpu0": [Interval("fusion", 0.0, 1.0),
                      Interval("fusion", 1.2, 2.0),
                      Interval("fusion", 2.1, 2.7),
                      Interval("fusion", 2.9, 4.0)]},
        host=[Interval("bench.window", 0.0, 4.0),
              Interval("bench.step", 0.9, 3.05),
              Interval("engine.step", 0.95, 2.6),
              Interval("engine.decode.dispatch", 0.95, 1.05),
              Interval("engine.sync", 1.05, 1.3),
              Interval("engine.decode.bookkeep", 2.0, 2.6)])
    s = xtrace.reduce(raw)
    assert s.idle_by_span == pytest.approx(
        {"engine.sync": 0.2, "engine.decode.bookkeep": 0.1,
         "bench.step": 0.2})
    assert scopes.engine_idle(s.idle_by_span) == pytest.approx(
        {"engine.sync": 0.2, "engine.decode.bookkeep": 0.1})
    assert scopes.engine_host_idle_ms(s.idle_by_span, 2) \
        == pytest.approx(150.0)
    assert scopes.engine_host_idle_ms(s.idle_by_span, 0) is None


def test_host_prefixes_keep_engine_spans():
    assert "engine.sync".startswith(scopes.HOST_PREFIXES)
    assert "bench.step".startswith(scopes.HOST_PREFIXES)
    assert not "jit_tick".startswith(scopes.HOST_PREFIXES)
