"""Jitted public wrapper for flash attention with GQA support and a pure-jnp
fallback (used on CPU / in dry-runs; the Pallas path targets TPU)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


def _expand_gqa(q, k, v):
    B, S, Hq, hd = q.shape
    K = k.shape[2]
    if K != Hq:
        rep = Hq // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return q, k, v


@functools.partial(jax.jit, static_argnames=("causal", "window", "impl",
                                             "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, impl: str = "pallas",
                    bq: int = 128, bk: int = 128, interpret: bool):
    """GQA flash attention. q: (B,S,Hq,hd); k,v: (B,S,K,hd), K | Hq."""
    q, k, v = _expand_gqa(q, k, v)
    if impl == "pallas":
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      bq=bq, bk=bk, interpret=interpret)
    return flash_attention_ref(q, k, v, causal=causal, window=window)
