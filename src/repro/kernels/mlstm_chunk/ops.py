"""Jitted wrapper for the chunkwise mLSTM kernel (+ sequential fallback)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.mlstm_chunk.kernel import mlstm_chunk_pallas
from repro.kernels.mlstm_chunk.ref import mlstm_ref


@functools.partial(jax.jit, static_argnames=("impl", "chunk", "interpret"))
def mlstm_chunk(q, k, v, log_i, log_f, *, impl: str = "pallas",
                chunk: int = 128, interpret: bool):
    """q,k,v: (B, S, hd); gates (B, S). Returns h (B, S, hd) fp32."""
    if impl == "pallas":
        return mlstm_chunk_pallas(q, k, v, log_i, log_f, chunk=chunk,
                                  interpret=interpret)
    hd = q.shape[-1]
    C0 = jnp.zeros((q.shape[0], hd, hd), jnp.float32)
    n0 = jnp.zeros((q.shape[0], hd), jnp.float32)
    m0 = jnp.full((q.shape[0],), -1e30, jnp.float32)
    h, _ = mlstm_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                     v.astype(jnp.float32), log_i.astype(jnp.float32),
                     log_f.astype(jnp.float32), C0, n0, m0)
    return h
