"""One benchmark run of one cell: build, warm up, measure, check, report.

The program is driven only through ``DisaggEngine``: ``submit``,
``run(max_iters=1)``, ``records``, ``finished``, each request's
``generated`` and the ``on_decode`` hook. Set-up also calls ``warmup`` and
replaces ``params`` with the benchmark's own weights.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

from servebench import spec, traffic as traffic_mod, work

SRC = spec.ROOT / "src"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ----------------------------------------------------------------------
# host-side record of the window
# ----------------------------------------------------------------------

@dataclasses.dataclass
class ReqLog:
    due: float                       # host clock, s
    submitted: float
    prompt_len: int
    out_tokens: int
    rid: int
    prefill_iter_start: Optional[float] = None
    stamps: List[float] = dataclasses.field(default_factory=list)
    prompt: Optional[np.ndarray] = None
    req: object = None               # the engine's request, once seen


@dataclasses.dataclass
class HostLog:
    t_open: float = 0.0
    t_close: float = 0.0
    requests: List[ReqLog] = dataclasses.field(default_factory=list)
    decode_steps: List[tuple] = dataclasses.field(default_factory=list)
    prefills: List[tuple] = dataclasses.field(default_factory=list)
    iterations: int = 0
    compiles: int = 0
    # (start, end, main thread's CPU seconds in between) per iteration
    iter_spans: List[tuple] = dataclasses.field(default_factory=list)
    harness_max_s: float = 0.0       # longest submit or stamp step
    gc_pauses: List[float] = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open


class Recorder:
    """``on_decode`` hook: notes which requests are live in each decode
    step and the positions each attends to. Host work only: it touches no
    device array."""

    def __init__(self):
        self.live: Dict[int, object] = {}
        self.steps: List[List[int]] = []

    def __call__(self, active, logits):
        keys = []
        for req in active.values():
            self.live[req.rec.rid] = req
            keys.append(len(req.tokens) + len(req.generated))
        self.steps.append(keys)


class CompileCounter:
    """Counts backend compiles and persistent-cache loads while on."""

    def __init__(self):
        import jax.monitoring as mon
        self.on = False
        self.count = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _event(self, event, **_):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.count += 1


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


# ----------------------------------------------------------------------
# set-up
# ----------------------------------------------------------------------

def device_or_fail(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (default device: {devs[0].platform}, "
                     f"{devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX found {len(devs)}")
    return devs[0]


class Session:
    """The engine of one cell with the benchmark's weights in it."""

    def __init__(self, c: dict, seed: int):
        import jax
        from repro.core.controller import ControllerConfig
        from repro.serving.engine import DisaggEngine
        self.c = c
        self.ref = spec.module("reference", c["family"])
        self.adapter = spec.module("adapters", c["family"])
        self.shape = self.ref.shape_of(c)
        sv = c["serving"]
        self.recorder = Recorder()
        self._gap_fns: dict = {}
        self._make = None
        self.eng = DisaggEngine(
            self.adapter.program_config(c),
            n_prefill=sv["prefill_workers"], n_decode=sv["decode_workers"],
            max_len=sv["max_len"], decode_slots=sv["decode_slots"],
            ctrl_cfg=ControllerConfig(), seed=seed % 2**31,
            on_decode=self.recorder)
        self.like = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self.eng.params)
        self.eng.params = None           # free the program's own weights
        self.set_weights(seed)

    def set_weights(self, seed: int):
        import jax
        self.eng.params = None
        self.weights = None
        gc.collect()
        if self._make is None:
            dtypes = self._dtypes()
            self._make = jax.jit(lambda key: self.ref.init_weights(
                self.shape, key, dtypes))
        self.weights = self._make(jax.random.key(seed))
        self.eng.params = self.adapter.to_program(self.weights, self.like)

    def _dtypes(self) -> dict:
        """Each weight's dtype: that of the program's leaf it becomes."""
        import jax
        names = self.adapter.layout(
            {k: k for k in self.ref.weight_shapes(self.shape)})
        return {n: a.dtype for n, a in zip(jax.tree_util.tree_leaves(names),
                                           jax.tree_util.tree_leaves(self.like))}

    def param_itemsize(self) -> int:
        import jax
        leaves = jax.tree_util.tree_leaves(self.eng.params)
        return max(a.dtype.itemsize for a in leaves)

    def kv_itemsize(self) -> int:
        """Itemsize of a decode worker's live cache, else the weights'."""
        import jax
        for w in getattr(self.eng, "workers", []):
            cache = getattr(w, "cache", None)
            if cache:
                return max(a.dtype.itemsize
                           for a in jax.tree_util.tree_leaves(cache))
        return self.param_itemsize()

    def gap_fn(self, control: bool):
        """The jitted reference (or control) gap function, built once."""
        import jax
        if control not in self._gap_fns:
            fn = self.ref.control_gaps if control else self.ref.served_gaps
            self._gap_fns[control] = jax.jit(
                lambda w, t, g: fn(w, self.shape, t, g))
        return self._gap_fns[control]

    def free_engine(self):
        self.eng = None
        self.recorder.live.clear()
        gc.collect()


def warm(sess: Session, ladder: List[int], seed: int) -> None:
    """Compile every prefill length of the ladder and the decode step,
    then serve enough requests at once that every decode slot, the ring
    and each ladder length go through the loop's eager ops."""
    eng = sess.eng
    for L in ladder:
        eng.warmup(L)
    slots = sess.c["serving"]["decode_slots"]
    n = max(len(ladder), slots)
    rng = np.random.default_rng([seed, 0x3a53])
    for i in range(n):
        L = ladder[i % len(ladder)]
        eng.submit(rng.integers(0, sess.shape.vocab, L).astype(np.int32),
                   n + 4, eng.clock)
    eng.run()
    sess.recorder.live.clear()
    sess.recorder.steps.clear()


# ----------------------------------------------------------------------
# the measured window
# ----------------------------------------------------------------------

def measure(sess: Session, gen: traffic_mod.Generator, seconds: float,
            counter: Optional[CompileCounter] = None) -> HostLog:
    """Drive the engine for ``seconds``: submit each request once it is
    due, then run one engine iteration; stamp every token new in that
    iteration with the iteration's end."""
    eng, rec = sess.eng, sess.recorder
    log = HostLog()
    clock = time.perf_counter
    sched = None if gen.is_backlog else gen.schedule()
    nxt = 0
    waiting: Dict[int, ReqLog] = {}          # rid -> not yet prefilled
    decoding: Dict[int, ReqLog] = {}         # rid -> first token seen
    by_rid: Dict[int, ReqLog] = {}
    depth = gen.backlog_depth()
    n_steps0 = len(rec.steps)

    def submit(r: traffic_mod.Request, due_abs: float):
        rid = len(eng.records)
        eng.submit(r.prompt, r.out_tokens, eng.clock)
        rl = ReqLog(due_abs, clock(), len(r.prompt), r.out_tokens, rid,
                    prompt=r.prompt)
        log.requests.append(rl)
        waiting[rid] = rl
        by_rid[rid] = rl

    if counter is not None:
        counter.count = 0
        counter.on = True
    gc_start: List[float] = []

    def gc_watch(phase, info):
        if phase == "start":
            gc_start.append(clock())
        elif gc_start:
            log.gc_pauses.append(clock() - gc_start.pop())
    gc.callbacks.append(gc_watch)
    with span("window"):
        t_open = log.t_open = clock()
        deadline = t_open + seconds
        while True:
            now = clock()
            if now >= deadline:
                break
            t_h = clock()
            with span("submit"):
                if sched is None:
                    while len(waiting) < depth:
                        submit(gen.take(now - t_open), now)
                else:
                    while nxt < len(sched) and t_open + sched[nxt].due <= now:
                        submit(sched[nxt], t_open + sched[nxt].due)
                        nxt += 1
            log.harness_max_s = max(log.harness_max_s, clock() - t_h)
            if not waiting and not decoding:
                wake = deadline if nxt >= len(sched) else min(
                    deadline, t_open + sched[nxt].due)
                with span("wait"):
                    time.sleep(max(wake - clock(), 0.0))
                continue
            t_s, cpu_s = clock(), time.thread_time()
            with span("run"):
                eng.run(max_iters=1)
            t_e = clock()
            log.iterations += 1
            log.iter_spans.append((t_s, t_e, time.thread_time() - cpu_s))
            with span("stamp"):
                for rid in [r for r in waiting
                            if eng.records[r].prefill_done is not None]:
                    rl = waiting.pop(rid)
                    rl.prefill_iter_start = t_s
                    rl.stamps.append(t_e)
                    decoding[rid] = rl
                    log.prefills.append((t_e, rl.prompt_len))
                for rid, req in list(rec.live.items()):
                    rl = by_rid.get(rid)
                    if rl is None:
                        continue
                    rl.req = req
                    new = len(req.generated) - len(rl.stamps)
                    rl.stamps.extend([t_e] * new)
                    if req.rec.finish is not None:
                        rec.live.pop(rid)
                        decoding.pop(rid, None)
                for keys in rec.steps[n_steps0:]:
                    log.decode_steps.append((t_e, keys))
                n_steps0 = len(rec.steps)
            log.harness_max_s = max(log.harness_max_s, clock() - t_e)
        log.t_close = clock()
    gc.callbacks.remove(gc_watch)
    if counter is not None:
        counter.on = False
        log.compiles = counter.count
    for rl in log.requests:
        if rl.req is None:
            rl.req = next((f for f in eng.finished if f.rec.rid == rl.rid),
                          None)
    return log


# ----------------------------------------------------------------------
# correctness
# ----------------------------------------------------------------------

def finished_in_window(log: HostLog) -> List[ReqLog]:
    return [r for r in log.requests
            if r.req is not None and len(r.stamps) >= r.out_tokens
            and len(r.req.generated) >= r.out_tokens]


def sample(done: List[ReqLog], seed: int, tokens: int,
           max_requests: int) -> List[ReqLog]:
    """Requests to check, drawn from ``seed``: the longest finished one,
    then others in a seeded order until ``tokens`` served tokens."""
    if not done:
        return []
    key = lambda r: (r.out_tokens, r.prompt_len, -r.rid)  # noqa: E731
    longest = max(done, key=key)
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 0xc4ec]).permutation(len(rest))
    out, n = [longest], longest.out_tokens
    for i in order:
        if n >= tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += rest[i].out_tokens
    return out


def gaps(sess: Session, reqs: List[ReqLog], *, control: bool = False):
    """Per sampled request, at each served position: the gap of the served
    token (with ``control``, of the token the int8 control puts first)
    below the reference's best logit, and the reference's own margin
    between its two best. Returns a list of (gaps, margins) pairs."""
    import jax.numpy as jnp
    max_len = sess.c["serving"]["max_len"]
    f = sess.gap_fn(control)
    out = []
    for r in reqs:
        served = list(r.req.generated[: r.out_tokens])
        seq = np.zeros(max_len, np.int32)
        tgt = np.full(max_len, -1, np.int32)
        P = len(r.prompt)
        seq[:P] = r.prompt
        seq[P: P + len(served) - 1] = served[:-1]
        tgt[P - 1: P - 1 + len(served)] = served
        g, m = f(sess.weights, jnp.asarray(seq), jnp.asarray(tgt))
        at = slice(P - 1, P - 1 + len(served))
        out.append((np.asarray(g)[at], np.asarray(m)[at]))
    return out


NEAR_TIE = 0.1    # logits; int8 weights move a logit by up to ~0.1 here


def compared(per_req, near_tie: float = NEAR_TIE) -> dict:
    """Readings over every sampled served position: the widest gap, the
    mean gap, and ``gap_per_near_tie``, the gaps' sum over the number of
    positions where the reference's two best logits lie within
    ``near_tie`` of each other. A sound program's gaps come only from such
    near ties, so the last reads the size of its error whether a seed's
    weights make many near ties or few. A configuration's ``limits`` name
    the readings that ``correct`` is decided on."""
    if not per_req:
        return {"max_gap": float("nan"), "mean_gap": float("nan"),
                "gap_per_near_tie": float("nan")}
    g = np.concatenate([g for g, _ in per_req])
    m = np.concatenate([m for _, m in per_req])
    ties = int((m < near_tie).sum())
    return {"max_gap": float(g.max()), "mean_gap": float(g.mean()),
            "gap_per_near_tie": float(g.sum() / max(ties, 1))}


# ----------------------------------------------------------------------
# reporting
# ----------------------------------------------------------------------

def pct(values, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values, float), q)) \
        if len(values) else None


def context(sess: Session, cell: dict, log: HostLog, trace, peak: dict,
            setup_s: float, param_itemsize: int, kv_itemsize: int):
    return SimpleNamespace(cell=cell, config=sess.c, host=log, trace=trace,
                           peak=peak, setup_s=setup_s, work=work,
                           decode_slots=sess.c["serving"]["decode_slots"],
                           param_itemsize=param_itemsize,
                           kv_itemsize=kv_itemsize, pct=pct, notes={})


def read_metrics(defs: list, kind: str, ctx) -> dict:
    out = {}
    for m in defs:
        v = spec.module(kind, m["name"]).read(ctx)
        if v is None:
            continue
        if not math.isfinite(v):
            raise ValueError(f"metric {m['name']} read {v}")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def say(*a):
    print("[bench]", *a, flush=True)


def describe(log: HostLog) -> None:
    """Sample counts and the loop's own health, on lines before the
    result."""
    reqs = log.requests
    late = [r.submitted - r.due for r in reqs]
    first_gap = [r.stamps[1] - r.stamps[0] for r in reqs if len(r.stamps) > 1]
    later = [b - a for r in reqs for a, b in zip(r.stamps[1:], r.stamps[2:])]
    live = [len(k) for _, k in log.decode_steps]
    longest = sorted(log.iter_spans, key=lambda se: se[0] - se[1])[:3]
    ms = lambda v: 1e3 * (v or 0)  # noqa: E731
    say(f"window {log.window_s:.4f} s, {log.iterations} engine iterations, "
        f"{len(log.decode_steps)} decode steps, {len(log.prefills)} prefills")
    say(f"requests due in window {len(reqs)}, with a first token "
        f"{sum(1 for r in reqs if r.stamps)}, finished "
        f"{len(finished_in_window(log))}, output tokens "
        f"{sum(len(r.stamps) for r in reqs)}")
    say(f"generator lateness ms: median {ms(pct(late, 50)):.3f}, p99 "
        f"{ms(pct(late, 99)):.3f}, max {ms(max(late, default=0)):.3f}")
    say(f"compiles inside the window: {log.compiles}")
    say("longest engine iterations (start s into the window, ms, main "
        "thread's CPU ms): "
        + ", ".join(f"{a - log.t_open:.3f} {ms(b - a):.3f} {ms(cpu):.3f}"
                    for a, b, cpu in longest)
        + f"; longest harness submit or stamp step ms "
        f"{ms(log.harness_max_s):.3f}; {len(log.gc_pauses)} garbage "
        f"collections in the window, longest "
        f"{ms(max(log.gc_pauses, default=0)):.3f} ms")
    say(f"first-to-second token gap ms (wait for a decode slot): median "
        f"{ms(pct(first_gap, 50)):.3f}, p90 {ms(pct(first_gap, 90)):.3f}; "
        f"later gaps p99 ms {ms(pct(later, 99)):.3f}; mean live decode "
        f"slots {np.mean(live) if live else 0:.3f}")


def check(sess: Session, log: HostLog, seed: int, *,
          control: bool = False):
    """Run the reference over a seeded sample of the finished requests.
    With ``control`` the int8 control stands in the program's place.
    Returns (readings, limits, requests checked)."""
    t0 = time.perf_counter()
    c = sess.c["check"]
    chosen = sample(finished_in_window(log), seed, c["sample_served_tokens"],
                    c["max_requests"])
    per_req = gaps(sess, chosen, control=control)
    readings = compared(per_req)
    say(f"{'control' if control else 'reference'} over {len(chosen)} "
        f"finished requests, {sum(len(g) for g, _ in per_req)} served "
        f"tokens, in {time.perf_counter() - t0:.3f} s")
    return readings, c["limits"], len(chosen)


def verdict(readings: dict, limits: dict, n_checked: int,
            compiles: int) -> bool:
    """``correct``: something was checked, nothing compiled in the window
    and every reading that has a limit is within it."""
    held = {k: lim for k, lim in limits.items() if lim is not None}
    return (n_checked > 0 and bool(held) and compiles == 0
            and all(readings[k] <= lim for k, lim in held.items()))


def serving_failures(log: HostLog, vocab: int) -> int:
    """Requests the engine finished in the window with another number of
    tokens than asked for, or with a token outside the vocabulary."""
    bad = 0
    for r in log.requests:
        req = r.req
        if req is None or req.rec.finish is None:
            continue
        toks = list(req.generated)
        if len(toks) != r.out_tokens or any(not 0 <= t < vocab
                                            for t in toks):
            bad += 1
    return bad


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        t_start: float, *, require_tpu: bool = True,
        overrides: Optional[dict] = None, control: bool = False) -> dict:
    """The whole run; returns the result line's object. ``overrides``
    (tests only) replaces the config, the traffic or the benchmark;
    ``control`` (tests and studies only) checks the int8 control in the
    program's place, which has to come out not correct."""
    import jax
    overrides = overrides or {}
    bench = overrides.get("benchmark") or spec.benchmark()
    cell = spec.cell(bench, cell_name)
    c = overrides.get("config") or spec.config(cell["config"])
    tspec = overrides.get("traffic") or spec.traffic(cell["traffic"])
    dev = device_or_fail(cell["chips"]) if require_tpu else jax.devices()[0]
    peak = spec.peak(dev.device_kind) if require_tpu else None
    say(f"device: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(jax.devices())}")
    counter = CompileCounter()

    sess = Session(c, seed)
    sv = c["serving"]
    gen = traffic_mod.Generator(tspec, seed, seconds, sv["max_len"],
                                sess.shape.vocab)
    warm(sess, sorted(tspec["prompt"]["ladder"]), seed)
    param_itemsize, kv_itemsize = sess.param_itemsize(), sess.kv_itemsize()
    jax.block_until_ready(sess.weights)

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="servebench-trace-")
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
    setup_s = time.perf_counter() - t_start
    log = measure(sess, gen, seconds, counter)
    if trace:
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")
    sess.free_engine()
    red = None
    if trace:
        from servebench import xtrace
        path = _find_xplane(trace_dir)
        events = xtrace.load(path)
        say("trace planes: " + json.dumps(events.planes))
        red = xtrace.reduce(events)
        _rmtree(trace_dir)

    describe(log)
    readings, limits, n_checked = check(sess, log, seed, control=control)
    correct = verdict(readings, limits, n_checked, log.compiles)

    ctx = context(sess, cell, log, red, peak, setup_s, param_itemsize,
                  kv_itemsize)
    if trace:
        metrics = read_metrics(
            spec.metrics_for(bench, "per_layer", cell_name), "layer_metrics",
            ctx)
    else:
        metrics = read_metrics(
            spec.metrics_for(bench, "end_to_end", cell_name), "e2e_metrics",
            ctx)
    if ctx.notes:
        say("notes: " + json.dumps(ctx.notes))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    result = {"correct": correct, "attempted": len(log.requests),
              "failed": serving_failures(log, sess.shape.vocab),
              "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in red.top_ops],
            "idle_gaps": [[n, s] for n, s in red.idle_by_label]}
        say("longest idle gaps, s: " + json.dumps(red.idle_gaps))
        say("idle by harness span, s: " + json.dumps(red.idle_by_span))
        say("device time per program, s: " + json.dumps(
            {k: [len(v), sum(v)] for k, v in red.module_times.items()}))
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in readings.items()}
    checks["compiles_in_window"] = {"value": log.compiles, "limit": 0}
    result["checks"] = checks
    for name, chk in checks.items():
        print(f"check {name}: {chk['value']} (limit {chk['limit']})",
              file=sys.stderr, flush=True)
    return result


def _trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _find_xplane(d: str) -> str:
    found = sorted(Path(d).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {d}")
    return str(found[-1])


def _rmtree(d: str) -> None:
    import shutil
    shutil.rmtree(d, ignore_errors=True)


def ensure_program_importable() -> None:
    if not (SRC / "repro").is_dir():
        raise FileNotFoundError(f"program not found under {SRC}")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
