"""Chip studies behind a cell's fixed numbers, in one process per call.

    python3 benchmarks/serve/study.py --traffic <traffic> sweep \\
        --rates 1.0,1.5,2.0 --seconds 30
    python3 benchmarks/serve/study.py --traffic <traffic> check \\
        --seeds 11,12,13 --seconds 20

``--traffic`` names a file under ``traffic/``; its configuration is the
file name's part before the first dot. A mix need not be a cell of
``BENCHMARK.json`` yet: the study is how a cell's rate and limits are
found before it goes in. One chip.

``sweep`` serves the cell's traffic at each offered rate and prints the
benchmark's end-to-end readings and how the queue (requests submitted
but not yet in a decode slot) moves over the window: the knee is the
highest rate at which it does not grow. ``check`` serves the cell's own
traffic on each seed's weights and puts the window through the
benchmark's own check twice: with the program's served tokens, and with
the int8 control in the program's place at the same served positions.
It prints each side's readings and verdict, and the window's loop
health (``run.describe``). The limits in ``configs/*.json`` are set from
these readings. Not part of a benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import numpy as np  # noqa: E402

from servebench import run, spec, traffic  # noqa: E402


def drain(sess):
    """Serve what is left, untimed, so the next window starts empty."""
    sess.eng.run()
    sess.recorder.live.clear()
    sess.recorder.steps.clear()


def queue_trace(log):
    """Requests submitted but not yet given their first decode step, at
    each iteration end (from the stamps)."""
    ends = sorted({t for r in log.requests for t in r.stamps})
    out = []
    for t in ends:
        n = sum(1 for r in log.requests
                if r.submitted <= t and (len(r.stamps) < 2 or r.stamps[1] > t))
        out.append((t - log.t_open, n))
    return out


def summarize(sess, bench, log):
    """The benchmark's own end-to-end readers (all but ``setup_s``) over
    one window."""
    ctx = run.context(sess, None, log, None, None, 0.0, 0, 0)
    defs = [m for m in bench["end_to_end"] if m["name"] != "setup_s"]
    row = {k: v["value"] for k, v in
           run.read_metrics(defs, "e2e_metrics", ctx).items()}
    row.update(requests=len(log.requests), iterations=log.iterations,
               compiles=log.compiles)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", required=True)
    ap.add_argument("mode", choices=("sweep", "check"))
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    run.ensure_program_importable()
    import jax
    bench = spec.benchmark()
    c = spec.config(args.traffic.split(".")[0])
    tspec = spec.traffic(args.traffic)
    run.device_or_fail(1)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    counter = run.CompileCounter()
    seeds = [int(s) for s in args.seeds.split(",") if s] or [1]
    sess = run.Session(c, seeds[0])
    run.warm(sess, sorted(tspec["prompt"]["ladder"]), seeds[0])
    print(f"[study] set-up {time.perf_counter() - T_START:.3f} s", flush=True)
    sv = c["serving"]
    if args.mode == "sweep":
        for rate in [float(r) for r in args.rates.split(",")]:
            t = dict(tspec, rate_rps=rate)
            gen = traffic.Generator(t, seeds[0], args.seconds, sv["max_len"],
                                    sess.shape.vocab)
            log = run.measure(sess, gen, args.seconds, counter)
            q = queue_trace(log)
            qs = [n for _, n in q]
            half = len(qs) // 2
            row = summarize(sess, bench, log)
            row.update(rate=rate, queue_first_half=float(np.mean(qs[:half])),
                       queue_second_half=float(np.mean(qs[half:])),
                       queue_end=qs[-1] if qs else 0,
                       queue_max=max(qs, default=0))
            print("[sweep] " + json.dumps(row), flush=True)
            drain(sess)
        return 0
    for seed in seeds:
        sess.set_weights(seed)
        jax.block_until_ready(sess.weights)
        gen = traffic.Generator(tspec, seed, args.seconds, sv["max_len"],
                                sess.shape.vocab)
        log = run.measure(sess, gen, args.seconds, counter)
        run.describe(log)
        row = {"seed": seed, "finished": len(run.finished_in_window(log)),
               "compiles": log.compiles}
        for side, control in (("program", False), ("control", True)):
            readings, limits, n = run.check(sess, log, seed, control=control)
            row[side] = dict(readings, requests=n, correct=run.verdict(
                readings, limits, n, log.compiles))
        print("[check] " + json.dumps(row), flush=True)
        drain(sess)
    return 0


if __name__ == "__main__":
    sys.exit(main())
