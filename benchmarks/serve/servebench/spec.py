"""Find a cell's files by name: ``BENCHMARK.json`` at the checkout's
root, ``configs/<config>.json``, ``traffic/<traffic>.json``,
``reference/<family>.py``, ``adapters/<family>.py``,
``e2e_metrics/<metric>.py``, ``layer_metrics/<metric>.py`` and
``peaks.json`` beside this package."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(BENCH_DIR / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def peak(device_kind: str) -> dict:
    table = load_json(BENCH_DIR / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json")
    return table[device_kind]


def module(kind: str, name: str):
    """``<kind>/<name>.py`` beside this package, imported by path (a name
    may hold dots). A dotted name without a file of its own falls back to
    the part before its last dot: ``device_idle_pct.serve`` and
    ``device_idle_pct.batch`` share ``device_idle_pct.py``."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file() and "." in name:
        return module(kind, name.rsplit(".", 1)[0])
    spec = importlib.util.spec_from_file_location(
        f"servebench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(bench: dict, section: str, cell_name: str) -> list:
    """Metrics of ``section`` that ``cell_name`` reports."""
    return [m for m in bench[section]
            if cell_name in m.get("workloads", [cell_name])]
