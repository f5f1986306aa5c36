"""Pallas TPU flash-attention (prefill) kernel.

TPU adaptation notes (vs the CUDA flash-attention algorithm):
  * the KV loop is the minor-most *grid* dimension, not an in-kernel loop —
    the TPU grid executes sequentially per core, so VMEM scratch
    (acc, m, l) persists across KV steps and plays the role of the CUDA
    thread-block registers;
  * block shapes are MXU-aligned (multiples of 128 on the matmul dims) and
    sized so q/k/v/acc tiles fit VMEM (~16 MB): bq=bk=128, hd<=256 claims
    ~0.5 MB across the four live tiles;
  * there is no warp-shuffle reduction: row max/sum are plain vector
    reductions over the lane dimension, which the VPU does natively.

Causal + sliding-window masking is applied inside the kernel; with causal
masking, KV blocks strictly above the diagonal are skipped via @pl.when.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               scale: float, causal: bool, window: Optional[int],
               bq: int, bk: int, n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk

    def body():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                             # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                    # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                   # (bk, hd)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    if causal:
        # skip blocks entirely above the causal diagonal
        @pl.when(k_start <= q_start + bq - 1)
        def _run():
            body()
    else:
        body()

    @pl.when(ki == n_kv - 1)
    def _final():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           bq: int = 128, bk: int = 128,
                           interpret: bool):
    """q,k,v: (B, S, H, hd) with identical H (GQA expansion done by caller).
    Returns (B, S, H, hd)."""
    B, S, H, hd = q.shape
    assert k.shape == v.shape == (B, S, H, hd)
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk
    scale = 1.0 / math.sqrt(hd)

    # fold (B, H) into one grid axis; per-step tiles are (1, bq/bk, hd)
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)

    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, n_kv=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
