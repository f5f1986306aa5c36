"""Pallas TPU chunkwise mLSTM kernel (stabilized linear-attention form).

TPU adaptation: the xLSTM CUDA kernels keep per-thread running state in
registers over the sequence; here the (hd x hd) matrix memory lives in VMEM
scratch and is carried across sequence-chunk grid steps (minor-most grid
dim). Within a chunk the quadratic intra-term uses two MXU matmuls
(q k^T and p v) with the log-space gate-decay matrix applied elementwise —
the same math as ``models/xlstm._mlstm_chunk_scan``, validated against the
exact sequential recurrence.

Grid: (B*nh, S/chunk). VMEM per step: q/k/v tiles (C x hd) + decay matrix
(C x C) + state (hd x hd + hd + 1) fp32; with C=128, hd=256 that is ~0.6 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, o_ref,
                  C_ref, n_ref, m_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        C_ref[...] = jnp.zeros_like(C_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)

    q = q_ref[0].astype(jnp.float32)              # (C, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    li = li_ref[0].astype(jnp.float32)            # (1, C) row vectors
    lf = lf_ref[0].astype(jnp.float32)

    # Gates arrive as lane rows. Column forms and the inclusive cumsum are
    # masked lane/sublane reductions of (C, C) broadcasts: vector ops only,
    # no cumsum primitive or vector transpose.
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = rows >= cols
    eye = rows == cols
    F_col = jnp.sum(jnp.where(tri, lf, 0.0), axis=1, keepdims=True)   # (C,1)
    F_row = jnp.sum(jnp.where(eye, F_col, 0.0), axis=0, keepdims=True)
    li_col = jnp.sum(jnp.where(eye, li, 0.0), axis=1, keepdims=True)
    # D[t,s] = F_t - F_s + li_s  (s <= t)
    D = jnp.where(tri, F_col - F_row + li, NEG)

    m_in = m_ref[...]                             # (1, 1)
    m_intra = jnp.max(D, axis=1, keepdims=True)   # (C, 1)
    m_inter = m_in + F_col
    m_row = jnp.maximum(m_intra, m_inter)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (C, C)
    w = s * jnp.exp(D - m_row)
    intra = jax.lax.dot_general(w, v, (((1,), (0,)), ((), ())))
    inter = jnp.exp(m_inter - m_row) * \
        jax.lax.dot_general(q, C_ref[...], (((1,), (0,)), ((), ())))
    qn = jnp.sum(q * n_ref[...], axis=1, keepdims=True)          # (C, 1)
    den = jnp.abs(jnp.sum(w, axis=1, keepdims=True) +
                  jnp.exp(m_inter - m_row) * qn)
    den = jnp.maximum(den, jnp.exp(-m_row))
    o_ref[0] = ((intra + inter) / den).astype(o_ref.dtype)

    # carry state to the next chunk
    FL = jnp.sum(lf, axis=1, keepdims=True)       # (1, 1)
    log_w = FL - F_col + li_col                   # (C, 1)
    m_next = jnp.maximum(m_in + FL, jnp.max(log_w, axis=0, keepdims=True))
    scale_old = jnp.exp(m_in + FL - m_next)
    kw = k * jnp.exp(log_w - m_next)              # (C, hd)
    C_ref[...] = C_ref[...] * scale_old + \
        jax.lax.dot_general(kw, v, (((0,), (0,)), ((), ())))
    n_ref[...] = n_ref[...] * scale_old + jnp.sum(kw, axis=0, keepdims=True)
    m_ref[...] = m_next


def mlstm_chunk_pallas(q, k, v, log_i, log_f, *, chunk: int = 128,
                       interpret: bool):
    """q,k,v: (B, S, hd) (fold heads into B); gates (B, S).
    Returns h (B, S, hd) fp32. Scaling of k (1/sqrt(hd)) is the caller's.

    The gates are passed as (B, 1, S) so their (1, 1, chunk) blocks are
    lane-aligned and span the full unit middle dim, as the TPU tiling
    rules require."""
    B, S, hd = q.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    kernel = functools.partial(_mlstm_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, 1, chunk), lambda b, c: (b, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((hd, hd), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, log_i.reshape(B, 1, S), log_f.reshape(B, 1, S))
