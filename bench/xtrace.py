"""Reduction of a profiler trace to device busy time, program and op times,
and idle gaps attributed to what the host was doing.

The harness records its own host spans (``bench.*``, through
``jax.profiler.TraceAnnotation``) on the same clock as the device events.
``load_xspace`` reads the ``.xplane.pb`` that ``jax.profiler`` writes;
``reduce`` works on plain intervals, so a test can hand it a synthetic trace.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "bench.window"
OTHER = "host.other"
_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")
_KERNEL = re.compile(r'kernel_name="?([A-Za-z_][A-Za-z0-9_]*)')


@dataclass
class Interval:
    name: str
    start: float          # seconds, trace clock
    end: float


@dataclass
class RawTrace:
    ops: dict = field(default_factory=dict)       # chip -> [Interval]
    modules: dict = field(default_factory=dict)   # chip -> [Interval]
    host: list = field(default_factory=list)      # [Interval] bench.* spans
    texts: dict = field(default_factory=dict)     # op label -> {HLO text}


@dataclass
class Summary:
    window: tuple                 # (start, end) seconds, trace clock
    busy_s: float                 # union of op intervals, mean over chips
    op_seconds: dict              # op label -> seconds, summed over chips
    op_texts: dict                # op label -> the HLO text of its events
    module_seconds: dict          # program name -> [seconds per execution]
    idle_by_span: dict            # host span name -> idle device seconds
    chips: int

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def modules_named(self, prefix: str) -> list:
        return [d for name, ds in self.module_seconds.items()
                if name.startswith(prefix) for d in ds]

    def ops_matching(self, text: str) -> float:
        """Seconds of the ops whose label or HLO text holds ``text``."""
        return sum(s for name, s in self.op_seconds.items()
                   if text in name or any(text in t for t in
                                          self.op_texts.get(name, ())))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def op_label(text: str) -> str:
    """A stable name for a device op from its trace name, which on a TPU is
    the HLO instruction (``%copy.169 = bf16[32,16,2048,64]{...} copy(...)``):
    the instruction without its numeric suffix and with the dtype and shape
    of its (first) result (``copy_bf16_32_16_2048_64``).  A Pallas kernel is
    named by its ``kernel_name`` where the trace keeps it, else
    ``tpu_custom_call`` with its result's shape."""
    k = _KERNEL.search(text)
    if k:
        return k.group(1)
    head, eq, rest = text.partition(" = ")
    base = re.sub(r"\.\d+$", "", head.strip().lstrip("%"))
    if 'custom_call_target="tpu_custom_call"' in rest:
        base = "tpu_custom_call"
    m = _SHAPE.match(rest.lstrip("(")) if eq else None
    if m:
        dims = m.group(2).replace(",", "_")
        return f"{base}_{m.group(1)}_{dims}" if dims else f"{base}_{m.group(1)}"
    return base


def union(intervals, lo: float, hi: float) -> list:
    """Merged (start, end) pairs of the intervals, clipped to [lo, hi]."""
    spans = sorted((max(i.start, lo), min(i.end, hi)) for i in intervals
                   if i.end > lo and i.start < hi)
    out = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list, lo: float, hi: float) -> list:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _innermost(spans: list, t: float) -> str:
    best = None
    for sp in spans:
        if sp.start <= t < sp.end and sp.name != WINDOW_SPAN:
            if best is None or sp.start > best.start:
                best = sp
    return best.name if best is not None else OTHER


def reduce(raw: RawTrace) -> Summary:
    """Busy time, op and program times and attributed idle gaps inside the
    host span ``bench.window``."""
    win = [h for h in raw.host if h.name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    lo, hi = win[0].start, win[0].end
    chips = sorted(raw.ops)
    if not chips:
        raise ValueError("trace holds no device op")
    busy_total = 0.0
    op_s: dict = defaultdict(float)
    idle: dict = defaultdict(float)
    spans = sorted(raw.host, key=lambda h: h.start)
    for chip in chips:
        ops = raw.ops[chip]
        busy = union(ops, lo, hi)
        busy_total += sum(e - s for s, e in busy)
        mine: dict = defaultdict(float)
        for i in ops:
            s, e = max(i.start, lo), min(i.end, hi)
            if e > s:
                mine[i.name] += e - s
        for name, sec in mine.items():
            if sec > hi - lo + 1e-9:
                raise ValueError(f"op {name!r} busy {sec} s in a {hi - lo} "
                                 f"s window on {chip}: overlapping events")
            op_s[name] += sec
        for s, e in gaps(busy, lo, hi):
            idle[_innermost(spans, 0.5 * (s + e))] += e - s
    mods: dict = defaultdict(list)
    for chip, evs in raw.modules.items():
        for i in evs:
            if i.start >= lo and i.end <= hi:
                mods[i.name].append(i.end - i.start)
    return Summary(window=(lo, hi), busy_s=busy_total / len(chips),
                   op_seconds=dict(op_s), op_texts=dict(raw.texts),
                   module_seconds=dict(mods),
                   idle_by_span=dict(idle), chips=len(chips))


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def load_xspace(logdir: str, host_prefix: str = "bench.") -> RawTrace:
    """Device ops and program executions of every TPU, and the harness's
    host spans, from the one ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData

    paths = sorted(Path(logdir).rglob("*.xplane.pb"))
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {logdir}, "
                         f"found {len(paths)}")
    pd = ProfileData.from_file(str(paths[0]))
    raw = RawTrace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            chip = plane.name
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs = raw.ops.setdefault(chip, [])
                    labels: dict = {}
                    for ev in line.events:
                        name = ev.name
                        if name not in labels:
                            labels[name] = op_label(name)
                            raw.texts.setdefault(labels[name],
                                                 set()).add(name)
                        evs.append(Interval(
                            labels[name], ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9))
                elif line.name == "XLA Modules":
                    evs = raw.modules.setdefault(chip, [])
                    for ev in line.events:
                        evs.append(Interval(_module_name(ev.name),
                                            ev.start_ns * 1e-9,
                                            (ev.start_ns + ev.duration_ns)
                                            * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        raw.host.append(Interval(
                            ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9))
    return raw
