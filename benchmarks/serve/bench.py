"""On-chip serving benchmark: one run of one cell.

    python3 benchmarks/serve/bench.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration and its
traffic are named in ``BENCHMARK.json``. The run builds ``DisaggEngine``
with the benchmark's seeded weights, warms up every shape the traffic
uses, drives the engine for ``--seconds`` under the cell's traffic, then
checks the served tokens against the plain reference. The last line of
stdout is one JSON object: the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics from a profiler trace with
``--trace 1``. Without a TPU it exits 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from servebench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        run.ensure_program_importable()
        from repro.launch.compile_cache import use_compile_cache
        run.say(f"compile cache: {use_compile_cache()}")
        result = run.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), T_START)
    except run.NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 3
    except FileNotFoundError as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
