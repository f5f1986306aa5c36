"""The reduction with the engine's spans (``engine_trace``) on constructed
traces, and the scheduler loop's readers on a constructed reduction and
host log."""
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from jax.profiler import ProfileData  # noqa: E402

from servebench import engine_trace, run, spec, xtrace  # noqa: E402
from servebench.xtrace import Ev  # noqa: E402
from test_servebench_trace import _events, _metadata, trace_text  # noqa: E402

READS = 10        # engine.to_host spans in the decode step, more than 8


def nested_text():
    """Window 0-200 ms. Host: bench.run 0-180 holds engine.decode 10-150,
    which holds engine.insert 10-20, engine.decode.step 20-60 and ten
    3 ms engine.to_host spans starting every 8 ms from 60; then
    engine.ctrl 150-160, engine.summarize 160-170; bench.stamp 180-200.
    Device: jit_scatter 12-14, jit_decode 25-55, and one 1 ms
    jit_dynamic_slice 1 ms into each engine.to_host."""
    reads = [60 + 8 * i for i in range(READS)]
    dev = [("jit_scatter(1)", 12, 2), ("jit_decode(2)", 25, 30)] + [
        ("jit_dynamic_slice(3)", r + 1, 1) for r in reads]
    dev_names = {"jit_scatter(1)": 1, "jit_decode(2)": 2,
                 "jit_dynamic_slice(3)": 3, "op": 4}
    host = ([("bench.window", 0, 200), ("bench.run", 0, 180),
             ("engine.decode", 10, 140), ("engine.insert", 10, 10),
             ("engine.decode.step", 20, 40)]
            + [("engine.to_host", r, 3) for r in reads]
            + [("engine.ctrl", 150, 10), ("engine.summarize", 160, 10),
               ("bench.stamp", 180, 20)])
    host_names = {n: i + 1 for i, n in enumerate(dict.fromkeys(
        n for n, _, _ in host))}
    modules = _events(dev, dev_names)
    ops = _events([("op", s, d) for _, s, d in dev], dev_names)
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000000 {modules} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000000 {ops} }}
  {_metadata(dev_names)} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 1000000 {_events(host, host_names)} }}
  {_metadata(host_names)} }}
"""


@pytest.fixture(scope="module")
def nested():
    pd = ProfileData.from_text_proto(nested_text())
    ev, engine = xtrace.from_profile(pd), engine_trace.engine_spans(pd)
    return ev, engine, engine_trace.reduce(ev, engine)


def test_engine_spans_are_read_beside_the_harness_spans(nested):
    ev, engine, _ = nested
    assert {s.name for s in ev.spans} == {"bench.window", "bench.run",
                                          "bench.stamp"}
    assert len(engine) == 5 + READS
    assert all(s.name.startswith("engine.") for s in engine)


def test_gaps_named_by_innermost_span_at_any_depth(nested):
    _, _, red = nested
    assert red.window_s == pytest.approx(0.200)
    assert red.busy_s == pytest.approx(0.042)
    # 9 gaps of 7 ms between sibling reads sit in engine.decode itself,
    # the last ones more than 8 spans after it started
    assert red.idle_by_span == pytest.approx({
        "bench.run": 0.012, "engine.insert": 0.011,
        "engine.decode.step": 0.006, "engine.decode": 0.063,
        "engine.summarize": 0.066})
    labels = dict(red.idle_by_label)
    assert labels["engine.decode after jit_dynamic_slice: 9 gaps, "
                  "longest 7.000 ms"] == pytest.approx(0.063)
    assert labels["engine.insert after jit_scatter: 1 gaps, "
                  "longest 11.000 ms"] == pytest.approx(0.011)
    assert labels["engine.decode.step after jit_decode: 1 gaps, "
                  "longest 6.000 ms"] == pytest.approx(0.006)
    assert red.idle_gaps[0] == ("engine.summarize after jit_dynamic_slice",
                                pytest.approx(0.066))
    assert "outside spans" not in red.idle_by_span


def test_program_spans_clipped_and_sorted(nested):
    _, _, red = nested
    spans = red.program_spans
    assert [s.start for s in spans] == sorted(s.start for s in spans)
    assert len(engine_trace.named(spans, "engine.to_host")) == READS
    assert len(engine_trace.inside(spans, "engine.to_host",
                                   "engine.decode")) == READS
    assert engine_trace.inside(spans, "engine.ctrl", "engine.decode") == []


def test_other_fields_are_xtrace_own(nested):
    ev, _, red = nested
    base = xtrace.reduce(ev)
    assert red.module_times == base.module_times
    assert red.top_ops == base.top_ops
    assert (red.window_s, red.busy_s) == (base.window_s, base.busy_s)


@pytest.mark.parametrize("recognised", [False, True])
def test_trace_without_engine_spans_reduces_as_before(recognised):
    pd = ProfileData.from_text_proto(trace_text())
    ev = xtrace.from_profile(pd)
    engine = engine_trace.engine_spans(pd) if recognised else []
    assert engine == []
    red = engine_trace.reduce(ev, engine)
    base = xtrace.reduce(ev)
    for f in xtrace.Reduction.__dataclass_fields__:
        assert getattr(red, f) == getattr(base, f), f
    assert red.program_spans == []


def test_nest_finds_innermost_like_a_scan():
    """A tree of spans five deep with a dozen siblings at each level,
    against the latest-starting span that holds each probe time."""
    spans = []

    def grow(start, end, depth):
        spans.append(Ev(f"d{depth}", start, end))
        if depth == 5:
            return
        step = (end - start) / 13
        for k in range(12):
            grow(start + (k + 0.5) * step, start + (k + 1) * step, depth + 1)
    grow(0.0, 13.0 ** 5, 0)
    nest = engine_trace.Nest(spans)
    rng = np.random.default_rng(7)
    for t in rng.uniform(-1, 13.0 ** 5 + 1, 400):
        holding = [s for s in spans if s.start <= t <= s.end]
        want = max(holding, key=lambda s: s.start) if holding else None
        assert nest.innermost(t) == want


def ctx_with(trace=None, requests=()):
    return SimpleNamespace(trace=trace, host=run.HostLog(
        requests=list(requests)), pct=run.pct)


def test_span_readers(nested):
    _, _, red = nested
    ctx = ctx_with(red)

    def read(name):
        return spec.module("layer_metrics", name).read(ctx)
    assert read("host_reads_per_step") == READS
    assert read("decode_readback_ms.serve") == pytest.approx(3.0 * READS)
    assert read("decode_readback_ms.batch") == pytest.approx(3.0 * READS)
    assert read("insert_ms") == pytest.approx(10.0)


@pytest.mark.parametrize("name", ["host_reads_per_step", "decode_readback_ms",
                                  "insert_ms", "token_gap_p99_ms"])
def test_readers_find_nothing_without_engine_spans(name):
    """The harness's own reduction, and requests without stamps, as a
    program without the engine's spans and counters gives them."""
    pd = ProfileData.from_text_proto(trace_text())
    req = SimpleNamespace(generated=[1, 2, 3])
    ctx = ctx_with(xtrace.reduce(xtrace.from_profile(pd)),
                   [run.ReqLog(0, 0, 8, 3, 0, req=req)])
    assert spec.module("layer_metrics", name).read(ctx) is None


def test_token_gap_reader():
    stamps = [[0.0, 0.010, 0.030, 0.060], [1.0, 1.005], [2.0]]
    reqs = [run.ReqLog(0, 0, 8, len(t), i, req=SimpleNamespace(token_t=t))
            for i, t in enumerate(stamps)]
    reqs.append(run.ReqLog(0, 0, 8, 4, 3))          # never seen: no request
    got = spec.module("layer_metrics", "token_gap_p99_ms").read(
        ctx_with(requests=reqs))
    want = np.percentile([10.0, 20.0, 30.0, 5.0], 99)
    assert got == pytest.approx(want)
