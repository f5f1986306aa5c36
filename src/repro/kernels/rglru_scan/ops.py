"""Jitted wrapper for the RG-LRU chunked scan (+ jnp fallback)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.rglru_scan.kernel import rglru_scan_pallas
from repro.kernels.rglru_scan.ref import rglru_scan_ref


@functools.partial(jax.jit, static_argnames=("impl", "chunk", "bw",
                                             "interpret"))
def rglru_scan(log_a, x, h0, *, impl: str = "pallas", chunk: int = 256,
               bw: int = 128, interpret: bool):
    if impl == "pallas":
        return rglru_scan_pallas(log_a, x, h0, chunk=chunk, bw=bw,
                                 interpret=interpret)
    return rglru_scan_ref(log_a, x, h0)
