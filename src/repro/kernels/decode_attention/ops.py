"""Jitted wrapper for split-KV decode attention (+ jnp fallback)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref


@functools.partial(jax.jit, static_argnames=("impl", "bs", "interpret"))
def decode_attention(q, k_cache, v_cache, pos, *, impl: str = "pallas",
                     bs: int = 512, interpret: bool):
    """q: (B, Hq, hd); caches (B, S, K, hd); pos: scalar current position."""
    if impl == "pallas":
        return decode_attention_pallas(q, k_cache, v_cache, pos, bs=bs,
                                       interpret=interpret)
    return decode_attention_ref(q, k_cache, v_cache, pos)
