"""Real-compute disaggregated serving engine.

Unlike ``core.simulator`` (analytic step times, used for the paper's power
experiments at MI300X scale), this engine runs *actual JAX forward passes*:
prefill workers fill real KV caches, the ring buffer hands the tensors to
decode workers, decode workers run continuous batching with per-slot
positions, and the SAME RapidController/PowerManager drive power and role
decisions. Power caps scale a logical clock (hardware power knobs cannot be
actuated from JAX), so the control loop sees the same dynamics end-to-end.

This is the mechanism-fidelity complement to the simulator: it proves the
KV handoff, per-slot batching, drain-and-flip role moves, and controller
integration on real tensors. Every worker runs on JAX's default device:
reduced configs on the CPU in the tests, published widths on one TPU
(``launch/serve.py``, ``chip_smoke.py``).

The engine's host work is named in the profiler's trace by spans
(``jax.profiler.TraceAnnotation``, always on, about a microsecond each
without a profiler): ``engine.prefill`` and ``engine.insert`` (arg
``rid``; the insert also ``state_bytes``, the bytes of the prefilled
state handed to the slot: KV and any convolution state),
``engine.decode`` around one decode iteration with
``engine.decode.step`` (token vector, dispatch, wait) inside it,
``engine.to_host`` around every device->host read, ``engine.on_decode``,
``engine.ctrl`` and ``engine.summarize``. ``ServeRequest.token_t`` holds
the host clock at which each generated token's value reached the host.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.controller import (ControllerConfig, Observation,
                                   RapidController)
from repro.core.goodput import RequestRecord, summarize
from repro.core.power_manager import PowerManager
from repro.core.power_model import PowerModel, mi300x
from repro.models import LM
from repro.serving.ring import KVRing


@dataclasses.dataclass
class ServeRequest:
    rec: RequestRecord
    tokens: np.ndarray               # prompt
    generated: list = dataclasses.field(default_factory=list)
    token_t: list = dataclasses.field(default_factory=list)  # perf_counter
    slot: int = -1                   # decode slot index


def _span(name: str, **args):
    """The host span ``engine.<name>`` in the profiler's trace; ``args``
    become the event's stats."""
    return jax.profiler.TraceAnnotation(f"engine.{name}", **args)


def _to_host(x, i: int) -> int:
    """One device->host read: ``int(x[i])``, the eager slice included,
    inside ``engine.to_host``."""
    with _span("to_host"):
        return int(x[i])


def _state_bytes(cache) -> int:
    """Bytes of a prefilled cache's state, its position aside."""
    return sum(a.nbytes for k, v in cache.items() if k != "pos"
               for a in jax.tree.leaves(v))


def _cache_insert(family: str, dst, src, slot: int):
    """Insert a batch-1 prefilled cache into slot ``slot`` of a batched
    decode cache: every stacked leaf (attention k/v, convolution state)
    has its batch dim at 1, hybrid 'rest' leaves at 0."""
    dst = dict(dst)
    src = dict(src)
    dst.pop("pos", None)
    src.pop("pos", None)

    def ins(path, d, s):
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        bdim = 0 if (keys and keys[0] == "rest") else 1
        idx = [slice(None)] * bdim + [slot]
        return d.at[tuple(idx)].set(jnp.squeeze(s, axis=bdim))
    return jax.tree_util.tree_map_with_path(ins, dst, src)


def build_steps(lm: LM):
    """The engine's jitted ``(prefill, decode)`` steps for ``lm``.

    ``prefill(params, tokens (B, S), cache) -> (last logits (B, V), cache)``;
    ``decode(params, tokens (B,), cache) -> (next (B,) int32, logits (B, V),
    cache)``. Module level so a compile-only check can lower exactly what
    the engine serves from parameter shapes, without building an engine.
    The benchmark's trace reduction finds the steps by their module names,
    ``jit_prefill`` and ``jit_decode``: keep the inner functions' names.
    """
    cfg = lm.cfg

    def prefill(p, toks, cache):
        batch = {"tokens": toks}
        if cfg.is_encoder_decoder:   # stubbed audio frontend embeddings
            batch["enc_feats"] = jnp.zeros(
                (toks.shape[0], cfg.encoder_seq, cfg.d_model), jnp.float32)
        return lm.prefill(p, batch, cache)

    def decode(p, tok, cache):
        logits, cache = lm.decode_step(p, tok, cache)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, cache

    return jax.jit(prefill), jax.jit(decode)


class Worker:
    def __init__(self, wid: int, role: str):
        self.wid = wid
        self.role = role
        self.draining = False
        self.free_at = 0.0           # logical clock
        # decode state
        self.active: dict = {}       # slot -> ServeRequest
        self.cache = None
        self.pos = None              # (B,) int32 per-slot positions


class DisaggEngine:
    def __init__(self, cfg: ModelConfig, *, n_prefill: int = 1,
                 n_decode: int = 1, max_len: int = 192,
                 decode_slots: int = 8, node_budget_w: float = 4800.0,
                 ctrl_cfg: Optional[ControllerConfig] = None,
                 power: Optional[PowerModel] = None, seed: int = 0,
                 caps: Optional[List[float]] = None,
                 time_scale: float = 1.0,
                 on_decode: Optional[Callable] = None):
        """``on_decode(active, logits)``, if given, is called after every
        decode step with the worker's ``{slot: ServeRequest}`` (before
        finished requests leave it) and the step's (decode_slots, V) logits
        on the device."""
        self.cfg = cfg
        self.lm = LM(cfg)
        self.params = jax.jit(self.lm.init, static_argnums=1)(
            jax.random.key(seed), jnp.float32)
        self.max_len = max_len
        self.decode_slots = decode_slots
        n = n_prefill + n_decode
        self.workers = ([Worker(i, "prefill") for i in range(n_prefill)] +
                        [Worker(n_prefill + i, "decode")
                         for i in range(n_decode)])
        self.pm = PowerManager(n, node_budget_w,
                               initial_caps=caps or [node_budget_w / n] * n)
        self.power = power or mi300x()
        self.ctrl = RapidController(ctrl_cfg, self.pm) if ctrl_cfg else None
        self.ctrl_cfg = ctrl_cfg
        self.ring = KVRing(32)
        self.queue: deque = deque()
        self.records: List[RequestRecord] = []
        self.finished: List[ServeRequest] = []
        self.clock = 0.0             # logical seconds
        self.time_scale = time_scale
        self.recent_ttft: deque = deque(maxlen=64)
        self.recent_tpot: deque = deque(maxlen=64)

        self.on_decode = on_decode
        # jitted steps (shared across workers; params are shared)
        self.prefill_step, self.decode_step = build_steps(self.lm)

    # ------------------------------------------------------------------
    def warmup(self, prompt_len: int) -> dict:
        """Compile the prefill step for ``(1, prompt_len)`` prompts and the
        decode step for ``decode_slots`` by running each once on zeros.
        Returns each first call's seconds: compile plus one execution."""
        toks = jnp.zeros((1, prompt_len), jnp.int32)
        cache = self.lm.init_cache(1, self.max_len, dtype=jnp.float32)
        t0 = time.perf_counter()
        jax.block_until_ready(self.prefill_step(self.params, toks, cache))
        t1 = time.perf_counter()
        cache = dict(self.lm.init_cache(self.decode_slots, self.max_len,
                                        dtype=jnp.float32))
        cache["pos"] = jnp.zeros((self.decode_slots,), jnp.int32)
        tok = jnp.zeros((self.decode_slots,), jnp.int32)
        t2 = time.perf_counter()
        jax.block_until_ready(self.decode_step(self.params, tok, cache))
        return {"prefill": t1 - t0, "decode": time.perf_counter() - t2}

    def submit(self, prompt: np.ndarray, out_tokens: int, now: float,
               ttft_slo=1.0, tpot_slo=0.04):
        rid = len(self.records)
        rec = RequestRecord(rid, now, len(prompt), out_tokens,
                            ttft_slo=ttft_slo, tpot_slo=tpot_slo)
        self.records.append(rec)
        self.queue.append(ServeRequest(rec, prompt))

    def _logical_dt(self, wall: float, role: str, wid: int) -> float:
        rel = self.power.rel(role, self.pm.effective[wid])
        return wall * self.time_scale / rel

    # ------------------------------------------------------------------
    def _do_prefill(self, w: Worker) -> bool:
        if not self.queue or self.ring.n_free == 0:
            return False
        req = self.queue.popleft()
        with _span("prefill", rid=req.rec.rid):
            toks = jnp.asarray(req.tokens)[None, :]
            cache = self.lm.init_cache(1, self.max_len, dtype=jnp.float32)
            t0 = time.perf_counter()
            logits, cache = self.prefill_step(self.params, toks, cache)
            jax.block_until_ready(logits)
            dt = self._logical_dt(time.perf_counter() - t0, "prefill", w.wid)
            self.clock = max(self.clock, w.free_at) + dt
            w.free_at = self.clock
            first = _to_host(jnp.argmax(logits, axis=-1), 0)
            req.token_t.append(time.perf_counter())
            req.rec.prefill_done = self.clock
            self.recent_ttft.append(req.rec.ttft)
            req.generated.append(first)
            assert self.ring.try_put((req, cache, first)) is not None
        return True

    def _ensure_decode_state(self, w: Worker):
        if w.cache is None:
            w.cache = dict(self.lm.init_cache(self.decode_slots, self.max_len,
                                              dtype=jnp.float32))
            w.cache.pop("pos", None)
            w.pos = jnp.zeros((self.decode_slots,), jnp.int32)

    def _admit(self, w: Worker):
        self._ensure_decode_state(w)
        while len(w.active) < self.decode_slots and self.ring.n_ready:
            req, cache, _first = self.ring.try_pull()
            slot = next(i for i in range(self.decode_slots)
                        if i not in {r.slot for r in w.active.values()})
            req.slot = slot
            with _span("insert", rid=req.rec.rid,
                       state_bytes=_state_bytes(cache)):
                w.cache = _cache_insert(self.cfg.family, w.cache, cache, slot)
                w.pos = w.pos.at[slot].set(len(req.tokens))
            w.active[slot] = req

    def _do_decode_iter(self, w: Worker) -> bool:
        with _span("decode"):
            self._admit(w)
            if not w.active:
                return False
            with _span("decode.step"):
                # feed each slot its last token (inactive slots feed 0)
                tok = np.zeros((self.decode_slots,), np.int32)
                for slot, req in w.active.items():
                    tok[slot] = req.generated[-1]
                cache = dict(w.cache)
                cache["pos"] = w.pos
                t0 = time.perf_counter()
                nxt, logits, cache = self.decode_step(
                    self.params, jnp.asarray(tok), cache)
                jax.block_until_ready(nxt)
            dt = self._logical_dt(time.perf_counter() - t0, "decode", w.wid)
            self.clock = max(self.clock, w.free_at) + dt
            w.free_at = self.clock
            self.recent_tpot.append(dt)
            w.pos = cache.pop("pos")
            w.cache = cache
            if self.on_decode is not None:
                with _span("on_decode"):
                    self.on_decode(w.active, logits)
            done = []
            for slot, req in list(w.active.items()):
                req.generated.append(_to_host(nxt, slot))
                req.token_t.append(time.perf_counter())
                if len(req.generated) >= req.rec.output_tokens or \
                        _to_host(w.pos, slot) >= self.max_len - 1:
                    req.rec.finish = self.clock
                    self.finished.append(req)
                    done.append(slot)
            for slot in done:
                del w.active[slot]
            return True

    # ------------------------------------------------------------------
    def _ctrl_tick(self):
        if self.ctrl is None:
            return
        self.pm.tick(self.clock)
        pre = [w.wid for w in self.workers if w.role == "prefill"
               and not w.draining]
        dec = [w.wid for w in self.workers if w.role == "decode"
               and not w.draining]
        obs = Observation(
            now=self.clock,
            ttft_p90=float(np.percentile(self.recent_ttft, 90))
            if self.recent_ttft else 0.0,
            tpot_p90=float(np.percentile(self.recent_tpot, 90))
            if self.recent_tpot else 0.0,
            q_prefill=len(self.queue), q_decode=self.ring.n_ready)
        d = self.ctrl.tick(obs, pre, dec)
        if d.kind == "power":
            src, dst = (dec, pre) if d.direction == "d2p" else (pre, dec)
            t_ready, freed = self.pm.shift(self.clock, src, dst,
                                           self.ctrl_cfg.power_step_w)
            self.pm.tick(t_ready)
            self.pm.apply_raise(t_ready, dst, freed,
                                self.ctrl_cfg.decode_cap_max_w
                                if d.direction == "p2d" else None)
        elif d.kind == "gpu":
            cands = dec if d.direction == "d2p" else pre
            if len(cands) > 1:
                w = self.workers[cands[-1]]
                if not w.active:     # drain-free flip for idle workers
                    w.role = ("prefill" if d.direction == "d2p" else "decode")
                    w.cache, w.pos, w.active = None, None, {}
                    self.clock += self.ctrl_cfg.gpu_move_drain_s
                    t_r, gpus, per = self.pm.distribute_uniform(self.clock)
                    self.pm.tick(t_r)
                    self.pm.apply_uniform(t_r, gpus, per)

    # ------------------------------------------------------------------
    def run(self, max_iters: int = 10_000):
        """Drive until all submitted requests finish."""
        it = 0
        while it < max_iters:
            it += 1
            progressed = False
            for w in self.workers:
                if w.role == "prefill":
                    progressed |= self._do_prefill(w)
                else:
                    progressed |= self._do_decode_iter(w)
            with _span("ctrl"):
                self._ctrl_tick()
            if not progressed:
                if all(r.finish is not None for r in self.records):
                    break
                self.clock += 0.01
        dur = max((r.finish or self.clock) for r in self.records) \
            if self.records else self.clock
        with _span("summarize"):
            return summarize(self.records, dur, sum(self.pm.effective))
