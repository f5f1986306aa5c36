"""The engine's spans in one traced window of a cell.

    python3 benchmarks/serve/spans.py --workload <cell> --seed <n> \\
        --seconds <s>

Run from the root of a checkout, on a TPU. Builds and warms the cell as
``bench.py`` does, traces one window, and prints one JSON line without
checking the served tokens: the cell's end-to-end metrics read from the
traced window (so they carry the profiler's cost), the scheduler loop's
metrics read from the engine's spans and counters (``SPAN_METRICS``), the
device idle time named by the innermost span of either kind around each
gap (``engine_trace.reduce``) beside the harness-only names
(``xtrace.reduce``), and each engine span's count, total and longest.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from servebench import engine_trace, run, spec, xtrace  # noqa: E402
from servebench import traffic as traffic_mod  # noqa: E402

# Readers in layer_metrics/ that need the engine's spans in the reduction.
SPAN_METRICS = [
    {"name": "host_reads_per_step", "unit": "reads"},
    {"name": "decode_readback_ms", "unit": "ms"},
    {"name": "insert_ms", "unit": "ms"},
    {"name": "token_gap_p99_ms", "unit": "ms"},
]


def span_totals(spans) -> dict:
    """Per engine span name: count, total seconds, longest ms."""
    by = defaultdict(list)
    for s in spans:
        by[s.name].append((s.end - s.start) * 1e-9)
    return {k: [len(v), sum(v), 1e3 * max(v)] for k, v in sorted(by.items())}


def traced_window(cell_name: str, seed: int, seconds: float) -> dict:
    import jax
    bench = spec.benchmark()
    cell = spec.cell(bench, cell_name)
    c, tspec = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    dev = run.device_or_fail(cell["chips"])
    sess = run.Session(c, seed)
    gen = traffic_mod.Generator(tspec, seed, seconds, c["serving"]["max_len"],
                                sess.shape.vocab)
    run.warm(sess, sorted(tspec["prompt"]["ladder"]), seed)
    param_itemsize, kv_itemsize = sess.param_itemsize(), sess.kv_itemsize()
    jax.block_until_ready(sess.weights)
    trace_dir = tempfile.mkdtemp(prefix="servebench-spans-")
    jax.profiler.start_trace(trace_dir,
                             profiler_options=run._trace_options())
    setup_s = time.perf_counter() - T_START
    counter = run.CompileCounter()
    log = run.measure(sess, gen, seconds, counter)
    jax.profiler.stop_trace()
    sess.free_engine()
    events, engine = engine_trace.load(run._find_xplane(trace_dir))
    run._rmtree(trace_dir)
    harness = xtrace.reduce(events)
    red = engine_trace.reduce(events, engine, top=20)
    ctx = run.context(sess, cell, log, red, spec.peak(dev.device_kind),
                      setup_s, param_itemsize, kv_itemsize)
    run.describe(log)
    in_run = harness.idle_by_span.get("bench.run", 0.0)
    in_engine = sum(v for k, v in red.idle_by_span.items()
                    if k.startswith(engine_trace.ENGINE_PREFIX))
    return {
        "cell": cell_name, "seed": seed, "compiles_in_window": log.compiles,
        "end_to_end": run.read_metrics(
            spec.metrics_for(bench, "end_to_end", cell_name), "e2e_metrics",
            ctx),
        "program": run.read_metrics(SPAN_METRICS, "layer_metrics", ctx),
        "window_s": red.window_s, "busy_s": red.busy_s,
        "idle_in_bench_run_s": in_run, "idle_in_engine_spans_s": in_engine,
        "idle_by_span": red.idle_by_span,
        "idle_by_label": red.idle_by_label,
        "longest_idle_gaps": red.idle_gaps,
        "harness_idle_by_label": harness.idle_by_label,
        "engine_spans": span_totals(red.program_spans),
        "longest_iterations": [
            [a - log.t_open, b - a, cpu] for a, b, cpu in
            sorted(log.iter_spans, key=lambda se: se[0] - se[1])[:3]],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    run.ensure_program_importable()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    try:
        out = traced_window(args.workload, args.seed, args.seconds)
    except run.NoChip as e:
        print(f"spans: {e}; no result", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
