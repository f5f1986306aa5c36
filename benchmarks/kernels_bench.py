"""Per-kernel microbenchmark: wall time of the interpret-mode Pallas kernels
vs their jnp oracles on CPU (correctness-oriented; TPU timings require real
hardware — block shapes and VMEM claims are validated structurally).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import Timer, save_artifact
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.rglru_scan.ops import rglru_scan


def timeit(fn, *args, n=3, **kw):
    fn(*args, **kw).block_until_ready() if hasattr(
        fn(*args, **kw), "block_until_ready") else None
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def main(fast: bool = False):
    tm = Timer().start()
    key = jax.random.key(0)
    rows = []
    # flash attention
    B, S, H, hd = 1, 256, 4, 64
    q = jax.random.normal(key, (B, S, H, hd), jnp.float32)
    us_p = timeit(flash_attention, q, q, q, impl="pallas", interpret=True)
    us_r = timeit(flash_attention, q, q, q, impl="ref", interpret=True)
    rows.append(("flash_attention", us_p, us_r))
    # decode attention
    q1 = jax.random.normal(key, (2, 8, 64), jnp.float32)
    kc = jax.random.normal(key, (2, 1024, 2, 64), jnp.float32)
    us_p = timeit(decode_attention, q1, kc, kc, 900, impl="pallas", interpret=True)
    us_r = timeit(decode_attention, q1, kc, kc, 900, impl="ref", interpret=True)
    rows.append(("decode_attention", us_p, us_r))
    # rglru
    la = -jnp.abs(jax.random.normal(key, (2, 512, 256))) * 0.1
    x = jax.random.normal(key, (2, 512, 256))
    h0 = jnp.zeros((2, 256))
    us_p = timeit(rglru_scan, la, x, h0, impl="pallas", interpret=True)
    us_r = timeit(rglru_scan, la, x, h0, impl="ref", interpret=True)
    rows.append(("rglru_scan", us_p, us_r))
    for name, us_p, us_r in rows:
        print(f"{name:18s} pallas(interpret) {us_p:10.0f}us  jnp-ref {us_r:10.0f}us")
    save_artifact("kernels_bench", timer=tm.stop(), payload=[
        {"kernel": n, "pallas_interpret_us": p, "ref_us": r}
        for n, p, r in rows])
    return rows


if __name__ == "__main__":
    main()
