"""Scheduler loop: device->host reads per decode step, counted where they
happen: the ``engine.to_host`` spans inside ``engine.decode`` spans of the
traced window, over its ``engine.decode.step`` spans. Needs a reduction
that keeps the engine's spans (``engine_trace.reduce``)."""
from servebench import engine_trace


def read(ctx):
    spans = getattr(ctx.trace, "program_spans", None)
    steps = len(engine_trace.named(spans or [], "engine.decode.step"))
    if not steps:
        return None
    reads = engine_trace.inside(spans, "engine.to_host", "engine.decode")
    return len(reads) / steps
