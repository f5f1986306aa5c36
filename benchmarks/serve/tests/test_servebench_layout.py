"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix and metric is found by its name alone, so a later change adds
a file and an entry and edits nothing."""
import json
import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from servebench import spec, traffic  # noqa: E402

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
WIDTH = re.compile(r"(_dim|_rank|_size|_width|_tok)$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/serve"]
    assert (spec.ROOT / BENCH["command"][1]).is_file()
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names():
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[section]]
        assert len(names) == len(set(names)), section
        assert all(NAME.match(n) for n in names), section


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files(c):
    path = spec.ROOT / c["file"]
    assert c["file"].startswith("benchmarks/serve/")
    f = json.loads(path.read_text())
    assert f["name"] == c["name"] and f["source"] == c["source"]
    assert sorted(f["reduced"]) == sorted(c["reduced"])
    for key in c["reduced"]:
        assert not WIDTH.search(key), key
        assert f[key] != f["source_values"][key], key
    assert (spec.BENCH_DIR / "reference" / f"{f['family']}.py").is_file()
    assert (spec.BENCH_DIR / "adapters" / f"{f['family']}.py").is_file()
    assert len(c["why"]) <= 200


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cells(w):
    assert w["chips"] == 1
    assert len(w["why"]) <= 200
    c = spec.config(w["config"])
    t = spec.traffic(w["traffic"])
    traffic.check_spec(t, c["serving"]["max_len"], c["vocab_size"])
    reports = {m["name"] for m in spec.metrics_for(BENCH, "end_to_end",
                                                    w["name"])}
    assert "setup_s" in reports and len(reports) >= 2
    layer = spec.metrics_for(BENCH, "per_layer", w["name"])
    assert layer
    for m in layer:
        assert m["moves"] in reports, (m["name"], w["name"])


@pytest.mark.parametrize("section,kind", [("end_to_end", "e2e_metrics"),
                                          ("per_layer", "layer_metrics")])
def test_every_metric_has_its_reader(section, kind):
    for m in BENCH[section]:
        assert callable(spec.module(kind, m["name"]).read), m["name"]
        assert m.get("workloads", True), m["name"]
        if section == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert m["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")


def test_peaks_refuse_an_unknown_device():
    assert spec.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peak("TPU v9 imaginary")


def test_dotted_metric_falls_back_to_its_stem():
    serve = spec.module("layer_metrics", "device_idle_pct.serve")
    batch = spec.module("layer_metrics", "device_idle_pct.batch")
    assert serve.__file__ == batch.__file__
    assert Path(serve.__file__).name == "device_idle_pct.py"
    with pytest.raises(FileNotFoundError):
        spec.module("layer_metrics", "no_such_metric")
