"""Serve granite_3_8b at its published widths on one TPU, and check it.

Run from the root of the repository: ``python chip_smoke.py``.

One process, through the normal serving entry point
(``repro.launch.serve.serve``): 16 seeded requests of 512 prompt tokens and
64 output tokens each pass through one prefill worker, the KV ring and one
decode worker with 8 slots. The model is granite_3_8b at its published
widths, cut to ``CHIP_LAYERS`` layers, with random f32 weights from the
seed. The script then checks that every request finished with its token
count, that every decode logit of a live slot was finite, and that one
request's decode logits from the batched engine agree with an isolated
batch-1 prefill and decode of the same request on the same device.

It exits non-zero, and prints no result, when JAX finds no TPU. On success
the last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "granite_3_8b"
SEED = 0
# Tolerance on |batched - isolated| decode logits, relative to the largest
# isolated logit. f32 matmuls at the TPU's default precision may round their
# operands to bf16 (relative step 2**-8). The batched (8-slot) and isolated
# (batch-1, scalar position) steps are different programs: they accumulate
# in different orders and need not round the same operands, so a logit can
# carry a different bf16 rounding error in each, compounded over the layers.
# 2e-2 is five bf16 steps of the largest logit. A wrong slot, position or
# stale cache row moves logits by tens of percent of it instead (a
# one-position slip measured 41% on the CPU).
LOGIT_RTOL = 2e-2


class DecodeProbe:
    """``DisaggEngine`` decode hook: keeps request ``rid``'s logits row of
    every decode step, and whether each live slot's logits were finite."""

    def __init__(self, rid: int):
        self.rid = rid
        self.rows: list = []
        self.finite: list = []       # (live slots, per-slot finite flags)

    def __call__(self, active, logits):
        self.finite.append((sorted(active),
                            jnp.isfinite(logits).all(axis=-1)))
        for slot, req in active.items():
            if req.rec.rid == self.rid:
                self.rows.append(logits[slot])

    def all_finite(self) -> bool:
        return all(bool(np.asarray(ok)[live].all())
                   for live, ok in self.finite)


def isolated_logits(eng, req):
    """Decode logits of ``req`` alone: batch-1 prefill, then batch-1 decode
    steps teacher-forced on the tokens the engine generated."""
    cache = eng.lm.init_cache(1, eng.max_len, dtype=jnp.float32)
    _, cache = eng.prefill_step(eng.params, jnp.asarray(req.tokens)[None],
                                cache)
    rows = []
    for tok in req.generated[:-1]:
        _, logits, cache = eng.decode_step(
            eng.params, jnp.asarray([tok], jnp.int32), cache)
        rows.append(logits[0])
    return jnp.stack(rows)


def smoke(cfg, traffic, *, seed: int = SEED) -> bool:
    """Serve ``traffic`` on ``cfg``, print what was measured, and return
    whether every check passed."""
    from repro.launch.serve import report, serve

    probe = DecodeProbe(rid=traffic.requests - 1)  # admitted to a reused slot
    run = serve(cfg, traffic, seed=seed, on_decode=probe)
    for line in report(run):
        print(line)
    eng = run.engine
    counts_ok = (len(eng.finished) == traffic.requests and
                 all(len(r.generated) == traffic.out_tokens
                     for r in eng.finished))
    finite_ok = probe.all_finite()
    req = next(r for r in eng.finished if r.rec.rid == probe.rid)
    ref = isolated_logits(eng, req)
    got = jnp.stack(probe.rows)
    diff = float(jnp.max(jnp.abs(got - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    logits_ok = (got.shape == ref.shape ==
                 (traffic.out_tokens - 1, cfg.vocab_size) and
                 diff <= LOGIT_RTOL * scale)
    print(f"[smoke] requests finished with {traffic.out_tokens} tokens: "
          f"{sum(len(r.generated) == traffic.out_tokens for r in eng.finished)}"
          f"/{traffic.requests}")
    print(f"[smoke] all live decode logits finite: {finite_ok}")
    print(f"[smoke] request {probe.rid}, {got.shape[0]} decode steps: "
          f"max |batched - isolated| logit {diff:.6g}, max |logit| "
          f"{scale:.6g}, tolerance {LOGIT_RTOL} x {scale:.6g} = "
          f"{LOGIT_RTOL * scale:.6g}")
    return counts_ok and finite_ok and logits_ok


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (default device: {dev.platform},"
              f" {dev.device_kind}); refusing to serve elsewhere",
              file=sys.stderr)
        return 1

    from repro.configs.base import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.serve import CHIP_LAYERS, Traffic, cut_depth, describe

    print(f"[smoke] compile cache: {use_compile_cache()}")
    full = get_config(ARCH)
    cfg = cut_depth(full, CHIP_LAYERS)
    traffic = Traffic()
    print(f"[smoke] {describe(cfg, full.n_layers)}, f32, seed {SEED}")
    print(f"[smoke] traffic: {traffic}")
    if not smoke(cfg, traffic):
        print("chip_smoke: a check failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
