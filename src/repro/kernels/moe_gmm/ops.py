"""Jitted wrapper for the grouped expert FFN: one layer of expert stacks or
a single layer's experts, rows padded to whole tiles."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm.kernel import moe_gmm_pallas

TM = 256          # rows per tile: a decode step's rows (slots x top-k) fit one
TF = 512          # hidden columns per block: 4 MiB of f32 per weight at K 2048


def plain_ffn(x, wg, wi, wo, offsets, layer):
    """``(silu(x[r] @ wg[e]) * (x[r] @ wi[e])) @ wo[e]`` for the rows of
    each expert of layer ``layer``, 0 past the last, at the matmul
    precision of the platform."""
    sizes = jnp.diff(offsets)

    def mm(a, w):
        wl = jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
        return jax.lax.ragged_dot(a, wl, sizes)
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wi), wo)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_gmm(x, wg, wi, wo, offsets, layer=0, *, interpret: bool):
    """x: (M, K), rows sorted by expert; wg, wi: (E, K, F) float32 and wo:
    (E, F, K) float32, or stacks (L, ...) of which layer ``layer`` is used;
    offsets: (E + 1,) int32, expert e's rows are ``[offsets[e], offsets[e
    + 1])``. Returns (M, K) float32: each row through its expert's SwiGLU
    FFN, every matmul with both operands rounded to bfloat16 and f32
    accumulation; rows past ``offsets[E]`` are undefined."""
    if wg.ndim == 3:
        wg, wi, wo = wg[None], wi[None], wo[None]
    offsets, layer = offsets.astype(jnp.int32), jnp.asarray(layer, jnp.int32)
    M = x.shape[0]
    tm = M if M <= TM else TM
    pad = -M % tm
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    F = wg.shape[-1]
    tf = TF if F % TF == 0 else F
    y = moe_gmm_pallas(xp, wg, wi, wo, offsets, layer, tm=tm, tf=tf,
                       interpret=interpret)
    return y[:M] if pad else y
