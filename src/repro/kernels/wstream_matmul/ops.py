"""Jitted wrapper for the weight-streaming matmul: any leading dims on
``x``, a 2-D weight or one layer of a stack, and either the layout the
TPU stores the weight in or a weight given as (N, K)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.wstream_matmul.kernel import LANE, wstream_matmul_pallas


def _stored_transposed(K: int, N: int) -> bool:
    """Whether the TPU's default layout keeps a (K, N) float32 array
    column-major: it does where only K is a multiple of 128 (e.g. an
    unembedding with an odd vocabulary). Read such a weight as (N, K),
    which is then a free view, not a copy."""
    return N % LANE != 0 and K % LANE == 0


@functools.partial(jax.jit, static_argnames=("interpret", "transposed"))
def wstream_matmul(x, w, layer=0, *, interpret: bool,
                   transposed: bool = False):
    """x: (..., K); w: (K, N) float32, or a stack (L, K, N) of which layer
    ``layer`` is used; with ``transposed``, w is (N, K) or (L, N, K) and
    the product is x @ w.T. Returns (..., N) float32: the product with both
    operands rounded to bfloat16 and f32 accumulation."""
    w = w if w.ndim == 3 else w[None]
    xf, layer = x.reshape(-1, x.shape[-1]), jnp.asarray(layer, jnp.int32)
    if not transposed and _stored_transposed(*w.shape[1:]):
        w, transposed = jnp.swapaxes(w, 1, 2), True
    y = wstream_matmul_pallas(xf, w, layer, transposed=transposed,
                              interpret=interpret)
    return y.reshape(*x.shape[:-1], y.shape[-1])
