"""The engine's span contract, on the CPU at the small granite size: the
``engine.*`` spans the benchmark's reduction reads are in the profiler's
host plane, nested as it expects, one ``engine.to_host`` per read, and
each request's token stamps keep step with its tokens."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from servebench import engine_trace, spec  # noqa: E402
from servebench_small import small_config  # noqa: E402

NAMES = {"engine.prefill", "engine.insert", "engine.decode",
         "engine.decode.step", "engine.to_host", "engine.on_decode",
         "engine.ctrl", "engine.summarize"}
PROMPTS = [8, 16, 8, 16, 8]
OUT = [3, 9, 6, 2, 12]


def serve(trace_dir=None):
    """Serve PROMPTS with 3 decode slots; returns the engine and, per
    decode step, each live request's (tokens so far, tokens asked for)."""
    import jax
    from repro.core.controller import ControllerConfig
    from repro.serving.engine import DisaggEngine
    c = small_config("granite_3_8b", slots=3)
    eng = DisaggEngine(spec.module("adapters", "dense_gqa").program_config(c),
                       max_len=c["serving"]["max_len"], decode_slots=3,
                       ctrl_cfg=ControllerConfig(), seed=3)
    steps = []
    eng.on_decode = lambda active, logits: steps.append(
        [(len(r.generated), r.rec.output_tokens) for r in active.values()])
    rng = np.random.default_rng(5)
    for L, n in zip(PROMPTS, OUT):
        eng.submit(rng.integers(0, c["vocab_size"], L).astype(np.int32), n,
                   0.0)
    if trace_dir is None:
        eng.run()
    else:
        with jax.profiler.trace(str(trace_dir)):
            eng.run()
    return eng, steps


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from jax.profiler import ProfileData
    d = tmp_path_factory.mktemp("engine-trace")
    eng, steps = serve(d)
    pd = ProfileData.from_file(str(next(d.rglob("*.xplane.pb"))))
    events = [e for plane in pd.planes if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith(engine_trace.ENGINE_PREFIX)]
    return eng, steps, events, engine_trace.engine_spans(pd)


def test_every_span_is_in_the_host_plane(traced):
    _, _, events, _ = traced
    assert {e.name for e in events} == NAMES


def test_reads_and_inserts_nest_in_their_step(traced):
    _, _, _, spans = traced
    inserts = engine_trace.named(spans, "engine.insert")
    assert engine_trace.inside(spans, "engine.insert",
                               "engine.decode") == inserts
    assert len(inserts) == len(PROMPTS)
    reads = engine_trace.named(spans, "engine.to_host")
    in_decode = engine_trace.inside(spans, "engine.to_host", "engine.decode")
    in_prefill = engine_trace.inside(spans, "engine.to_host",
                                     "engine.prefill")
    assert len(in_prefill) == len(PROMPTS)
    assert sorted(in_decode + in_prefill, key=lambda s: s.start) == reads


def test_one_read_per_token_and_per_position_checked(traced):
    """Each live slot reads its token; its position too unless the token
    was its last."""
    _, steps, _, spans = traced
    stepped = [t.start for t in engine_trace.named(spans,
                                                   "engine.decode.step")]
    decodes = [d for d in engine_trace.named(spans, "engine.decode")
               if any(d.start <= t <= d.end for t in stepped)]
    assert len(decodes) == len(steps)
    reads = engine_trace.named(spans, "engine.to_host")
    for d, live in zip(decodes, steps):
        got = sum(1 for r in reads if d.start <= r.start and r.end <= d.end)
        assert got == sum(1 if g + 1 >= n else 2 for g, n in live)


def test_prefill_and_insert_carry_the_request_id(traced):
    eng, _, events, _ = traced
    rids = {r.rid for r in eng.records}
    for name in ("engine.prefill", "engine.insert"):
        got = [v for e in events if e.name == name
               for k, v in e.stats if k == "rid"]
        assert sorted(got) == sorted(rids), name


def test_token_stamps_keep_step_with_tokens():
    eng, _ = serve()
    assert len(eng.finished) == len(PROMPTS)
    for req in eng.finished:
        assert len(req.generated) == req.rec.output_tokens
        assert len(req.token_t) == len(req.generated)
        assert all(b >= a for a, b in zip(req.token_t, req.token_t[1:]))
