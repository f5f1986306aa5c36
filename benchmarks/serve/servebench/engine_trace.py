"""The engine's own spans in a profiler trace, beside what ``xtrace`` reads.

``DisaggEngine`` opens ``engine.*`` spans (``jax.profiler.TraceAnnotation``)
around its host work: ``engine.decode`` holds ``engine.insert``,
``engine.decode.step``, ``engine.on_decode`` and one ``engine.to_host`` per
device->host read, so spans nest three deep with a dozen siblings. Here
each idle gap of the window is named by the innermost span of either kind
(``bench.*`` or ``engine.*``) around it, found at any nesting depth, and
the engine spans clipped to the window are kept as ``program_spans`` for
the scheduler loop's readers. A trace with no engine span reduces to
exactly what ``xtrace.reduce`` gives.
"""
from __future__ import annotations

import bisect
import dataclasses
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from servebench import xtrace
from servebench.xtrace import Ev

ENGINE_PREFIX = "engine."


def engine_spans(pd) -> List[Ev]:
    """Host events named ``engine.*`` in a ``jax.profiler.ProfileData``."""
    return [Ev(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(ENGINE_PREFIX)]


def load(path: str) -> Tuple[xtrace.Events, List[Ev]]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return xtrace.from_profile(pd), engine_spans(pd)


class Nest:
    """Spans of one thread, which nest: the innermost around a time, at any
    depth. Each span's parent is the innermost span open at its start; the
    spans around ``t`` are the latest span starting before ``t`` and its
    ancestors, so the walk up the parents stops at the first that holds
    ``t``."""

    def __init__(self, spans: List[Ev]):
        self.spans = sorted(spans, key=lambda s: (s.start, -s.end))
        self.starts = [s.start for s in self.spans]
        self.parent: List[int] = []
        open_: List[int] = []
        for i, s in enumerate(self.spans):
            while open_ and self.spans[open_[-1]].end < s.start:
                open_.pop()
            self.parent.append(open_[-1] if open_ else -1)
            open_.append(i)

    def innermost(self, t: float) -> Optional[Ev]:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.spans[i].end < t:
            i = self.parent[i]
        return self.spans[i] if i >= 0 else None


@dataclasses.dataclass
class Reduction(xtrace.Reduction):
    program_spans: List[Ev] = dataclasses.field(default_factory=list)


def reduce(ev: xtrace.Events, engine: List[Ev], top: int = 10) -> Reduction:
    """``xtrace.reduce`` with the idle gaps named by the innermost span of
    either kind around their midpoints, and the engine spans clipped to
    the window as ``program_spans``."""
    base = xtrace.reduce(ev, top)
    t0, t1 = next((s.start, s.end) for s in ev.spans
                  if s.name == xtrace.WINDOW_SPAN)
    program = sorted(xtrace._clip(engine, t0, t1), key=lambda s: s.start)
    nest = Nest([s for s in ev.spans if s.name != xtrace.WINDOW_SPAN
                 and s.end > t0 and s.start < t1] + program)
    gaps: List[Tuple[str, float]] = []
    idle_by_span: Dict[str, float] = defaultdict(float)
    for dev in sorted(set(ev.ops) | set(ev.modules)):
        ops = xtrace._clip(ev.ops.get(dev, []), t0, t1)
        mods = sorted(xtrace._clip(ev.modules.get(dev, []), t0, t1),
                      key=lambda e: e.start)
        busy = xtrace.union([(e.start, e.end) for e in (ops or mods)])
        starts = [m.start for m in mods]
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            around = nest.innermost((a + b) / 2)
            span = around.name if around else "outside spans"
            i = bisect.bisect_left(starts, a) - 1
            label = f"{span} after {mods[i].name}" if i >= 0 else span
            idle_by_span[span] += (b - a) * 1e-9
            gaps.append((label, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    by_label: Dict[str, List[float]] = defaultdict(list)
    for label, sec in gaps:
        by_label[label].append(sec)
    totals = sorted(((f"{lab}: {len(v)} gaps, longest {max(v) * 1e3:.3f} ms",
                      sum(v)) for lab, v in by_label.items()),
                    key=lambda kv: -kv[1])
    fields = {f.name: getattr(base, f.name)
              for f in dataclasses.fields(xtrace.Reduction)}
    fields.update(idle_gaps=gaps[:top], idle_by_label=totals[:top],
                  idle_by_span=dict(idle_by_span))
    return Reduction(**fields, program_spans=program)


def inside(spans: List[Ev], inner: str, outer: str) -> List[Ev]:
    """The spans named ``inner`` that lie inside a span named ``outer``
    (spans of one name do not overlap one another)."""
    outers = sorted((s for s in spans if s.name == outer),
                    key=lambda s: s.start)
    starts = [s.start for s in outers]
    out = []
    for s in spans:
        if s.name != inner:
            continue
        i = bisect.bisect_right(starts, s.start) - 1
        if i >= 0 and outers[i].end >= s.end:
            out.append(s)
    return out


def named(spans: List[Ev], name: str) -> List[Ev]:
    return [s for s in spans if s.name == name]
