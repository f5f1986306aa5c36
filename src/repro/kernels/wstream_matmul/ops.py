"""Jitted wrapper for the weight-streaming matmul: any leading dims on
``x``, a 2-D weight or one layer of a stack, either the layout the TPU
stores the weight in or a weight given as (N, K), and a backward pass."""
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _stream(x, w, layer, interpret, transposed):
    if transposed:
        return wstream_matmul_pallas(x, w, layer, transposed=True,
                                     interpret=interpret)
    K, N = w.shape[1:]
    if _stored_transposed(K, N):
        return wstream_matmul_pallas(x, jnp.swapaxes(w, 1, 2), layer,
                                     transposed=True, interpret=interpret)
    return wstream_matmul_pallas(x, w, layer, interpret=interpret)


def _stream_fwd(x, w, layer, interpret, transposed):
    return _stream(x, w, layer, interpret, transposed), (x, w, layer)


def _stream_bwd(interpret, transposed, res, g):
    x, w, layer = res
    wl = jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
    wl = wl.T if transposed else wl
    dx = (g @ wl.T).astype(x.dtype)
    dwl = x.T.astype(w.dtype) @ g
    dw = jnp.zeros_like(w).at[layer].set(dwl.T if transposed else dwl)
    return dx, dw, None


_stream.defvjp(_stream_fwd, _stream_bwd)


@functools.partial(jax.jit, static_argnames=("interpret", "transposed"))
def wstream_matmul(x, w, layer=0, *, interpret: bool,
                   transposed: bool = False):
    """x: (..., K); w: (K, N) float32, or a stack (L, K, N) of which layer
    ``layer`` is used; with ``transposed``, w is (N, K) or (L, N, K) and
    the product is x @ w.T. Returns (..., N) float32: the product with both
    operands rounded to bfloat16 and f32 accumulation."""
    w = w if w.ndim == 3 else w[None]
    y = _stream(x.reshape(-1, x.shape[-1]), w, jnp.asarray(layer, jnp.int32),
                interpret, transposed)
    return y.reshape(*x.shape[:-1], y.shape[-1])
