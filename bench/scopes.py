"""Device time of the decode tick by the model's named scopes, and device
idle by the engine's host spans.

The model programs name their ops with ``jax.named_scope`` (``embed``,
``attention`` with ``kv_write`` inside it, ``mlp``, ``head``), which reaches
each HLO instruction as the ``op_name`` of its metadata.  A TPU's ``XLA Ops``
trace events carry no ``op_name``: an event is named by its instruction
(``%copy.116 = bf16[32,16,2048,64]{...} copy(...)``).  So ``ScopeMap`` reads
the compiled programs' HLO text (``compiled.as_text()``, see ``tick_texts``)
and maps each instruction to the innermost known scope of its ``op_name``;
``scope_seconds`` sums the device time of the ops that ran inside ``jit_tick``
executions by that scope, and an op with none (XLA's layout copies of a cache
parameter carry the parameter's name) under ``unscoped``.

The engine's host spans (``engine.*``, ``repro.serving.tracing``) sit on the
same clock as the device's ops; ``xtrace.reduce`` already puts each idle gap
in the innermost host span around its midpoint, so a trace loaded with
``HOST_PREFIXES`` moves the idle of the harness's ``bench.step`` into the
engine's phases.  ``engine_idle`` and ``engine_host_idle_ms`` read that.

These are plain functions of HLO text and intervals; the harness does not
call them yet.
"""
from __future__ import annotations

import bisect
import os
import re
from collections import defaultdict
from pathlib import Path

from bench import xtrace

SCOPES = ("embed", "attention", "kv_write", "mlp", "head")
UNSCOPED = "unscoped"
TICK = "jit_tick"
ENGINE = "engine."
HOST_PREFIXES = ("bench.", ENGINE)

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = (.*)$")
_COMP = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"calls=%([^\s,)}]+)")


def scope_of(op_name: str):
    """The innermost known scope in an op's name path, or None.  A fused op
    joins its parts' paths with ';': the first path that names a scope
    decides."""
    for path in op_name.split(";"):
        for part in reversed(path.split("/")):
            if part in SCOPES:
                return part
    return None


def instructions(hlo_text: str) -> dict:
    """Instruction name -> (its line without the metadata, its op_name) of
    every computation of one HLO module's text.  An instruction with no
    metadata that calls a computation (a fusion XLA built) takes the op_name
    of that computation's root."""
    out, roots, calls = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        c = _COMP.match(line)
        if c:
            comp = c.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        body, _, meta = rest.partition(", metadata={")
        on = _OP_NAME.search(meta)
        op_name = on.group(1) if on else ""
        out[name] = (f"%{name} = {body}", op_name)
        if line.lstrip().startswith("ROOT") and comp is not None:
            roots[comp] = name
        k = _CALLS.search(body)
        if k and not op_name:
            calls[name] = k.group(1)
    for name, comp in calls.items():
        root = roots.get(comp)
        if root is not None and root in out:
            out[name] = (out[name][0], out[root][1])
    return out


def instruction_name(event: str) -> str:
    """``copy.116`` of a trace event named ``%copy.116 = bf16[...] ...``."""
    return event.partition(" = ")[0].strip().lstrip("%")


class ScopeMap:
    """Scope of each instruction of one or more compiled programs (the tick
    of every granularity the engine serves).  Instruction names repeat
    across programs; where they name different scopes, the program whose
    instruction text shares the longest prefix with the event's decides."""

    def __init__(self, hlo_texts):
        self._by_name: dict = defaultdict(list)
        self._seen: dict = {}          # event text -> scope: ticks repeat
        for text in hlo_texts:
            for name, (line, op_name) in instructions(text).items():
                self._by_name[name].append((line, scope_of(op_name)))

    def scope(self, event: str) -> str:
        s = self._seen.get(event)
        if s is None:
            s = self._seen[event] = self._scope(event)
        return s

    def _scope(self, event: str) -> str:
        cands = self._by_name.get(instruction_name(event), ())
        scopes = {s for _, s in cands}
        if len(scopes) > 1:
            best = max(cands, key=lambda c: len(os.path.commonprefix(
                [c[0], event])))
            scopes = {best[1]}
        s = next(iter(scopes), None)
        return s if s is not None else UNSCOPED


def _tick_spans(modules: list, lo: float, hi: float) -> list:
    return sorted((m.start, m.end) for m in modules
                  if m.name.startswith(TICK) and m.start >= lo and m.end <= hi)


def scope_seconds(ops: dict, modules: dict, window: tuple,
                  smap: ScopeMap) -> dict:
    """Device seconds, summed over chips, of the ops whose midpoint lies in
    a ``jit_tick`` execution inside ``window``, by scope: every key of
    ``SCOPES`` and ``UNSCOPED``, each op in exactly one.  ``ops`` and
    ``modules`` map a chip to its ``xtrace.Interval``s; an op's ``name`` is
    its raw trace event name."""
    lo, hi = window
    out = dict.fromkeys(SCOPES + (UNSCOPED,), 0.0)
    for chip, evs in ops.items():
        ticks = _tick_spans(modules.get(chip, ()), lo, hi)
        starts = [s for s, _ in ticks]
        for ev in evs:
            mid = 0.5 * (ev.start + ev.end)
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and mid < ticks[i][1]:
                out[smap.scope(ev.name)] += ev.end - ev.start
    return out


def ticks_in(modules: dict, window: tuple) -> int:
    """``jit_tick`` executions inside ``window``, summed over chips."""
    return sum(len(_tick_spans(ms, *window)) for ms in modules.values())


def per_tick_ms(scope_s: dict, n_ticks: int) -> dict:
    return {k: 1e3 * v / n_ticks for k, v in scope_s.items()} if n_ticks \
        else {}


def tick_attention_ms(scope_s: dict, n_ticks: int):
    """Device ms a tick of the ops in ``attention`` or ``kv_write``."""
    if not n_ticks:
        return None
    return 1e3 * (scope_s["attention"] + scope_s["kv_write"]) / n_ticks


def scope_line(scope_s: dict, n_ticks: int) -> str:
    """The per-scope ms a tick, largest first, for a run's ``trace:`` log;
    they sum to the tick programs' op time a tick."""
    ms = per_tick_ms(scope_s, n_ticks)
    parts = ", ".join(f"{k} {v:.3f}" for k, v in
                      sorted(ms.items(), key=lambda kv: -kv[1]))
    return (f"tick scopes, ms a tick over {n_ticks} ticks: {parts}; "
            f"sum {sum(ms.values()):.3f}")


def engine_idle(idle_by_span: dict) -> dict:
    """The idle seconds ``xtrace.reduce`` put in each ``engine.*`` span."""
    return {k: v for k, v in idle_by_span.items() if k.startswith(ENGINE)}


def engine_host_idle_ms(idle_by_span: dict, n_steps: int):
    """Device-idle ms an engine step whose gap lies in an ``engine.*``
    span."""
    if not n_steps:
        return None
    return 1e3 * sum(engine_idle(idle_by_span).values()) / n_steps


def tick_texts(eng, granularities) -> list:
    """HLO text of the engine's compiled decode tick for each boundary list
    in ``granularities``, lowered with the engine's own arrays so that the
    program (and its instruction names) is the one it runs."""
    import jax.numpy as jnp
    B = eng.ecfg.max_batch
    tok = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    tables = jnp.asarray(eng.block_tables) if eng.ecfg.paged else None
    out = []
    for bounds in granularities:
        prog, _ = eng.executors.fused_decode(tuple(bounds))
        out.append(prog.lower(eng.caches, tok, pos, tables)
                   .compile().as_text())
    return out


def load_ops(logdir: str) -> tuple:
    """(ops, modules) of every TPU in the one ``.xplane.pb`` under
    ``logdir``: chip -> ``xtrace.Interval``s, ops named by their raw event
    name (the instruction), programs as ``xtrace.load_xspace`` names them."""
    from jax.profiler import ProfileData
    paths = sorted(Path(logdir).rglob("*.xplane.pb"))
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {logdir}, "
                         f"found {len(paths)}")
    pd = ProfileData.from_file(str(paths[0]))
    ops: dict = {}
    modules: dict = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:") or "Core" in plane.name:
            continue
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            dest = ops if line.name == "XLA Ops" else modules
            evs = dest.setdefault(plane.name, [])
            for ev in line.events:
                name = ev.name if dest is ops else \
                    re.sub(r"\(\d+\)$", "", ev.name)
                evs.append(xtrace.Interval(
                    name, ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9))
    return ops, modules
