"""Pallas TPU split-KV decode-attention kernel (flash-decoding style).

The long KV cache is split across the minor grid dimension; a VMEM scratch
accumulator carries the running (max, sum, weighted-V) across KV blocks —
the TPU-idiomatic replacement for the GPU flash-decoding pattern, where
partial results from thread blocks are combined by a second reduction
kernel (warp shuffles have no TPU analogue; the sequential grid + VMEM
scratch achieves the same reduction without a second pass).

GQA layout: queries arrive as (B, K, G, hd) — one kernel instance per
(batch, kv-head); the G query heads sharing that KV head are processed as
the matmul's row dimension, so the KV block is loaded once per G rows
(the GQA arithmetic-intensity win, preserved in VMEM). The caches are
viewed as (B, S, K*hd) (a free reshape), so the k/v block of head k is the
(bs, hd) column slab k: lane-aligned whenever hd is a multiple of 128.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dec_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                *, scale: float, bs: int, n_kv: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[0]
    start = si * bs

    @pl.when(start <= pos)
    def _run():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (G, hd)
        k = k_ref[0].astype(jnp.float32)                   # (bs, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, bs)
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                   # (bs, hd)
        acc_ref[...] = acc_ref[...] * alpha + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(si == n_kv - 1)
    def _final():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, pos, *, bs: int = 512,
                            interpret: bool):
    """q: (B, Hq, hd); caches (B, S, K, hd); pos scalar int32.
    Returns (B, Hq, hd)."""
    B, Hq, hd = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = Hq // K
    bs = min(bs, S)
    assert S % bs == 0
    ns = S // bs
    qg = q.reshape(B, K, G, hd)
    kf = k_cache.reshape(B, S, K * hd)
    vf = v_cache.reshape(B, S, K * hd)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    kernel = functools.partial(_dec_kernel, scale=1.0 / math.sqrt(hd),
                               bs=bs, n_kv=ns)
    out = pl.pallas_call(
        kernel,
        grid=(B, K, ns),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, hd), lambda b, k, s: (b, k, 0, 0)),
            pl.BlockSpec((1, bs, hd), lambda b, k, s: (b, s, k)),
            pl.BlockSpec((1, bs, hd), lambda b, k, s: (b, s, k)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, k, s: (b, k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(pos_arr, qg, kf, vf)
    return out.reshape(B, Hq, hd)
