"""Seeded request generator, driven by a traffic file's parameters.

Lengths, their order and the gaps between arrivals come from the file's
``shape_seed``; a run's ``seed`` gives the token ids. So every seed gets
the same schedule of the same work: at 0.8 of the knee, which requests
wait for a decode slot depends on the order of arrivals and lengths, and
a per-seed order moved the tails of `granite_3_8b.chat` by ~50% from
seed to seed (against ~5% between two runs of one seed).

- ``"arrival": "poisson"``: ``round(rate_rps * seconds)`` requests whose
  exponential gaps are scaled to fill the window exactly (a Poisson
  process conditioned on its count). Each request is due at its arrival.
- ``"arrival": "backlog"``: an endless supply, handed out so that at least
  ``backlog`` requests wait for prefill at all times. Each is due when it
  is handed over.

Prompt lengths are log-normal (``median``, ``sigma``) rounded up to the
next length of ``ladder`` (and capped at its last); output lengths are
log-normal, rounded, clipped to ``[min, max]``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

POOL = 4096                      # length pairs in a backlog's fixed multiset


@dataclasses.dataclass(frozen=True)
class Request:
    idx: int
    due: float                   # seconds after the window opens
    prompt: np.ndarray           # int32 token ids
    out_tokens: int


def lengths(spec: dict, n: int, rng: np.random.Generator):
    """``n`` (prompt, output) length pairs drawn from ``spec``."""
    p, o = spec["prompt"], spec["output"]
    ladder = np.asarray(sorted(p["ladder"]))
    raw = rng.lognormal(np.log(p["median"]), p["sigma"], n)
    idx = np.minimum(np.searchsorted(ladder, raw), len(ladder) - 1)
    prompts = ladder[idx]
    outs = np.clip(np.rint(rng.lognormal(np.log(o["median"]), o["sigma"], n)),
                   o["min"], o["max"]).astype(int)
    return prompts.astype(int), outs


def check_spec(spec: dict, max_len: int, vocab: int) -> None:
    """Refuse a traffic file whose longest request would not fit the
    engine: prompt plus output must stay within ``max_len - 1``."""
    longest = max(spec["prompt"]["ladder"]) + spec["output"]["max"]
    if longest > max_len - 1:
        raise ValueError(f"longest request {longest} tokens exceeds "
                         f"max_len - 1 = {max_len - 1}")
    if spec["arrival"] not in ("poisson", "backlog"):
        raise ValueError(f"unknown arrival {spec['arrival']!r}")
    if vocab < 2:
        raise ValueError("vocabulary too small")


class Generator:
    """Requests of one run: ``schedule()`` for a Poisson cell, ``take()``
    for a backlog cell."""

    def __init__(self, spec: dict, seed: int, seconds: float, max_len: int,
                 vocab: int):
        check_spec(spec, max_len, vocab)
        self.spec = spec
        self.seconds = float(seconds)
        self.vocab = vocab
        self.rng = np.random.default_rng([seed, 0x5e7e])
        shape = np.random.default_rng(spec["shape_seed"])
        if spec["arrival"] == "poisson":
            n = max(int(round(spec["rate_rps"] * self.seconds)), 1)
            gaps = shape.exponential(1.0, n + 1)
            gaps *= self.seconds / gaps.sum()
            prompts, outs = lengths(spec, n, shape)
        else:
            gaps = None
            prompts, outs = lengths(spec, POOL, shape)
        self.prompts, self.outs, self.gaps = prompts, outs, gaps
        self.handed = 0

    @property
    def is_backlog(self) -> bool:
        return self.gaps is None

    def _make(self, i: int, due: float) -> Request:
        L = int(self.prompts[i % len(self.prompts)])
        toks = self.rng.integers(0, self.vocab, L, dtype=np.int64)
        return Request(i, due, toks.astype(np.int32),
                       int(self.outs[i % len(self.outs)]))

    def schedule(self) -> List[Request]:
        """Every request of a Poisson cell, in due order."""
        assert not self.is_backlog
        due = np.cumsum(self.gaps[:-1])
        reqs = [self._make(i, float(t)) for i, t in enumerate(due)]
        self.handed = len(reqs)
        return reqs

    def take(self, due: float) -> Request:
        """The next backlog request, due at ``due``."""
        assert self.is_backlog
        r = self._make(self.handed, due)
        self.handed += 1
        return r

    def backlog_depth(self) -> Optional[int]:
        return self.spec.get("backlog") if self.is_backlog else None
