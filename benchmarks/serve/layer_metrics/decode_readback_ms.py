"""Scheduler loop: host time of the device->host reads per decode step,
the eager slices included: the summed ``engine.to_host`` spans inside
``engine.decode`` spans of the traced window, over its
``engine.decode.step`` spans (ms). One reader for each split of the
metric (``.serve``, ``.batch``) by the end-to-end metric it moves. Needs a
reduction that keeps the engine's spans (``engine_trace.reduce``)."""
from servebench import engine_trace


def read(ctx):
    spans = getattr(ctx.trace, "program_spans", None)
    steps = len(engine_trace.named(spans or [], "engine.decode.step"))
    if not steps:
        return None
    reads = engine_trace.inside(spans, "engine.to_host", "engine.decode")
    return 1e-6 * sum(s.end - s.start for s in reads) / steps
