"""Trace reduction on a constructed trace: busy and idle time, device time
per program, top ops, and idle gaps named by harness span."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from jax.profiler import ProfileData  # noqa: E402

from servebench import xtrace  # noqa: E402

MS = 10**9        # picoseconds per millisecond


def _events(spec, names):
    return " ".join(
        f"events {{ metadata_id: {names[n]} offset_ps: {int(s * MS)} "
        f"duration_ps: {int(d * MS)} }}" for n, s, d in spec)


def _metadata(names):
    return " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                    for n, i in names.items())


def trace_text():
    """Window 0-100 ms. Host: run 0-40, stamp 40-50, run 50-90,
    wait 90-100. Device: decode 5-25 (ops 5-15, 15-25), prefill 55-75
    (one op 55-75), decode 78-88 (op 78-88)."""
    dev_names = {"jit_decode(7)": 1, "jit_prefill(9)": 2,
                 "%fusion.1 = f32[8] fusion(f32[8] %p)": 3, "dot.2": 4,
                 "fusion.3": 5}
    host_names = {"bench.window": 1, "bench.run": 2, "bench.stamp": 3,
                  "bench.wait": 4, "other": 5}
    modules = _events([("jit_decode(7)", 5, 20), ("jit_prefill(9)", 55, 20),
                       ("jit_decode(7)", 78, 10)], dev_names)
    fusion = "%fusion.1 = f32[8] fusion(f32[8] %p)"
    ops = _events([(fusion, 5, 10), ("dot.2", 15, 10),
                   ("fusion.3", 55, 20), (fusion, 78, 10)], dev_names)
    spans = _events([("bench.window", 0, 100), ("bench.run", 0, 40),
                     ("bench.stamp", 40, 10), ("bench.run", 50, 40),
                     ("bench.wait", 90, 10), ("other", 10, 5)], host_names)
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000000 {modules} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000000 {ops} }}
  {_metadata(dev_names)} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 1000000 {spans} }}
  {_metadata(host_names)} }}
planes {{ id: 3 name: "/device:TPU_NON_CORE:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000000 {ops} }}
  {_metadata(dev_names)} }}
"""


@pytest.fixture(scope="module")
def reduction():
    ev = xtrace.from_profile(ProfileData.from_text_proto(trace_text()))
    return ev, xtrace.reduce(ev)


def test_reads_only_tpu_cores_and_harness_spans(reduction):
    ev, red = reduction
    assert list(ev.ops) == ["/device:TPU:0"]
    assert red.n_devices == 1
    assert {s.name for s in ev.spans} == {"bench.window", "bench.run",
                                          "bench.stamp", "bench.wait"}


def test_busy_and_idle(reduction):
    _, red = reduction
    assert red.window_s == pytest.approx(0.100)
    assert red.busy_s == pytest.approx(0.050)
    assert red.idle_share == pytest.approx(0.5)


def test_device_time_per_program(reduction):
    _, red = reduction
    assert sorted(red.module_times) == ["jit_decode", "jit_prefill"]
    assert red.step_times("decode") == pytest.approx([0.020, 0.010])
    assert red.step_times("prefill") == pytest.approx([0.020])


def test_top_ops_named_by_program(reduction):
    _, red = reduction
    ops = dict(red.top_ops)
    assert len(ops) == 3 and red.top_ops[-1][0] == "jit_decode/dot.2"
    assert ops["jit_prefill/fusion.3"] == pytest.approx(0.020)
    assert ops["jit_decode/fusion.1"] == pytest.approx(0.020)
    assert ops["jit_decode/dot.2"] == pytest.approx(0.010)


def test_idle_gaps_named_by_span_and_previous_program(reduction):
    _, red = reduction
    # gaps: 0-5 (run), 25-55 (stamp at its middle, 40), 75-78 (run),
    # 88-100 (wait at its middle, 94)
    assert red.idle_gaps[0] == ("bench.stamp after jit_decode",
                                pytest.approx(0.030))
    labels = dict(red.idle_gaps)
    assert labels["bench.wait after jit_decode"] == pytest.approx(0.012)
    assert labels["bench.run after jit_prefill"] == pytest.approx(0.003)
    assert labels["bench.run"] == pytest.approx(0.005)
    assert sum(red.idle_by_span.values()) == pytest.approx(0.050)
    assert red.idle_by_label[0] == (
        "bench.stamp after jit_decode: 1 gaps, longest 30.000 ms",
        pytest.approx(0.030))
    assert len(red.idle_by_label) == 4


def test_window_and_device_are_required():
    ev = xtrace.Events(ops={}, modules={}, spans=[])
    with pytest.raises(ValueError, match="bench.window"):
        xtrace.reduce(ev)
    ev.spans.append(xtrace.Ev("bench.window", 0, 10))
    with pytest.raises(ValueError, match="device"):
        xtrace.reduce(ev)


def test_union_merges_overlaps():
    assert xtrace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
