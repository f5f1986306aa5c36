"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

Device events are the ``XLA Ops`` and ``XLA Modules`` lines of each
``/device:TPU:<n>`` plane (an op is named by the program it ran in); host spans are the harness's own
``TraceAnnotation``s (names starting ``bench.``) on the host plane. The
window is the ``bench.window`` span. Busy time is the union of op
intervals inside the window, averaged over the devices; each idle gap is
named by the innermost harness span around its midpoint and by the
program (module) that ran before it. The gaps are also summed by that
label, which says what the host was doing while the chip waited.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"\(\d+\)$")
_DEVICE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass(frozen=True)
class Ev:
    name: str
    start: float                 # ns
    end: float                   # ns


@dataclasses.dataclass
class Events:
    ops: Dict[str, List[Ev]]         # device plane -> op executions
    modules: Dict[str, List[Ev]]     # device plane -> program executions
    spans: List[Ev]                  # harness spans on the host
    planes: Dict[str, List[str]] = dataclasses.field(default_factory=dict)


def module_name(raw: str) -> str:
    """``jit_decode(123)`` -> ``jit_decode``."""
    return _SUFFIX.sub("", raw)


def op_name(raw: str) -> str:
    """``%fusion.3 = f32[8] fusion(...)`` -> ``fusion.3``."""
    return raw.split(" = ", 1)[0].lstrip("%")


def from_profile(pd) -> Events:
    """Pull device ops, device modules and harness spans from a
    ``jax.profiler.ProfileData``."""
    ops: Dict[str, List[Ev]] = {}
    modules: Dict[str, List[Ev]] = {}
    spans: List[Ev] = []
    planes: Dict[str, List[str]] = {}
    for plane in pd.planes:
        planes[plane.name] = [line.name for line in plane.lines]
        if _DEVICE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out = ops.setdefault(plane.name, [])
                    for e in line.events:
                        out.append(Ev(op_name(e.name), e.start_ns,
                                      e.start_ns + e.duration_ns))
                elif line.name == "XLA Modules":
                    out = modules.setdefault(plane.name, [])
                    for e in line.events:
                        out.append(Ev(module_name(e.name), e.start_ns,
                                      e.start_ns + e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Ev(e.name, e.start_ns,
                                        e.start_ns + e.duration_ns))
    return Events(ops, modules, spans, planes)


def load(path: str) -> Events:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(evs: List[Ev], t0: float, t1: float) -> List[Ev]:
    return [Ev(e.name, max(e.start, t0), min(e.end, t1))
            for e in evs if e.end > t0 and e.start < t1]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                            # mean over devices
    n_devices: int
    module_times: Dict[str, List[float]]     # module -> seconds per call
    top_ops: List[Tuple[str, float]]         # (op, seconds), largest first
    idle_gaps: List[Tuple[str, float]]       # (label, seconds), longest first
    idle_by_label: List[Tuple[str, float]]   # (label, total s), largest first
    idle_by_span: Dict[str, float]           # span -> idle seconds in it

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def step_times(self, word: str) -> List[float]:
        """Seconds per call of every module whose name holds ``word``."""
        return [t for m, ts in self.module_times.items() if word in m
                for t in ts]


def _innermost(spans: List[Ev], starts: List[float], t: float) -> str:
    """Name of the latest-starting span around ``t``; ``spans`` sorted by
    start, ``starts`` their starts."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(i - 8, -1), -1):     # harness spans barely nest
        if spans[j].end >= t:
            return spans[j].name
    return "outside spans"


def reduce(ev: Events, top: int = 10) -> Reduction:
    win = [s for s in ev.spans if s.name == WINDOW_SPAN]
    if not win:
        raise ValueError("trace holds no bench.window span")
    t0, t1 = win[0].start, win[0].end
    spans = sorted((s for s in ev.spans if s.name != WINDOW_SPAN
                    and s.end > t0 and s.start < t1), key=lambda s: s.start)
    span_starts = [s.start for s in spans]
    devices = sorted(set(ev.ops) | set(ev.modules))
    if not devices:
        raise ValueError("trace holds no device plane")
    busy_total = 0.0
    op_time: Dict[str, float] = defaultdict(float)
    module_times: Dict[str, List[float]] = defaultdict(list)
    gaps: List[Tuple[str, float]] = []
    idle_by_span: Dict[str, float] = defaultdict(float)
    for dev in devices:
        ops = _clip(ev.ops.get(dev, []), t0, t1)
        mods = sorted(_clip(ev.modules.get(dev, []), t0, t1),
                      key=lambda e: e.start)
        basis = ops if ops else mods
        busy = union([(e.start, e.end) for e in basis])
        busy_total += sum(e - s for s, e in busy)
        starts = [m.start for m in mods]
        for e in ops:
            i = bisect.bisect_right(starts, e.start) - 1
            name = f"{mods[i].name}/{e.name}" if i >= 0 else e.name
            op_time[name] += (e.end - e.start) * 1e-9
        for m in mods:
            module_times[m.name].append((m.end - m.start) * 1e-9)
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            span = _innermost(spans, span_starts, (a + b) / 2)
            i = bisect.bisect_left(starts, a) - 1
            label = f"{span} after {mods[i].name}" if i >= 0 else span
            idle_by_span[span] += (b - a) * 1e-9
            gaps.append((label, (b - a) * 1e-9))
    n = len(devices)
    gaps.sort(key=lambda g: -g[1])
    by_label: Dict[str, List[float]] = defaultdict(list)
    for label, sec in gaps:
        by_label[label].append(sec)
    totals = sorted(((f"{lab}: {len(v)} gaps, longest {max(v) * 1e3:.3f} ms",
                      sum(v)) for lab, v in by_label.items()),
                    key=lambda kv: -kv[1])
    return Reduction(
        window_s=(t1 - t0) * 1e-9, busy_s=busy_total * 1e-9 / n,
        n_devices=n, module_times=dict(module_times),
        top_ops=sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=gaps[:top], idle_by_label=totals[:top],
        idle_by_span=dict(idle_by_span))
