"""Scheduler loop: mean host time of one admission's insert into the
decode cache, the ``engine.insert`` spans of the traced window (ms). The
copies themselves run as ``jit_scatter`` device ops. Needs a reduction
that keeps the engine's spans (``engine_trace.reduce``)."""
from servebench import engine_trace


def read(ctx):
    spans = engine_trace.named(
        getattr(ctx.trace, "program_spans", None) or [], "engine.insert")
    if not spans:
        return None
    return 1e-6 * sum(s.end - s.start for s in spans) / len(spans)
