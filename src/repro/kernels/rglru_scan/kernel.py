"""Pallas TPU chunked linear-recurrence kernel for RG-LRU.

TPU adaptation: the GPU implementations of Griffin use a per-thread
sequential scan over registers. On TPU we instead:
  * tile (batch, width) across the outer grid — each (bi, wi) tile is an
    independent recurrence over S;
  * walk sequence chunks on the minor grid dimension; the recurrent carry
    h lives in VMEM scratch across chunk steps;
  * inside a chunk, the scan is computed with a log2(C) associative
    doubling ladder of vector ops (VPU-friendly) rather than C sequential
    steps: (a, b) o (a', b') = (a*a', a'*b + b') composed over strides
    1, 2, 4, ... — numerically identical to the sequential recurrence.

VMEM: a (bw x C) fp32 tile pair plus the (1, bw) carry; bw=128 lanes,
C=256 -> ~0.3 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(log_a_ref, x_ref, h0_ref, o_ref, carry_ref, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        carry_ref[...] = h0_ref[0].astype(jnp.float32)      # (1, bw)

    la = log_a_ref[0].astype(jnp.float32)       # (C, bw)
    x = x_ref[0].astype(jnp.float32)            # (C, bw)

    # associative doubling ladder over the chunk (axis 0); rows shifted in
    # by the roll's wrap-around are masked out
    a = la
    b = x
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    stride = 1
    while stride < chunk:
        a_shift = pltpu.roll(a, stride, 0)
        b_shift = pltpu.roll(b, stride, 0)
        mask = row >= stride
        b = jnp.where(mask, jnp.exp(a) * b_shift + b, b)
        a = jnp.where(mask, a + a_shift, a)
        stride *= 2
    # a = cumulative log decay from chunk start; b = scan with h=0 carry-in
    h = b + jnp.exp(a) * carry_ref[...]
    o_ref[0] = h.astype(o_ref.dtype)
    carry_ref[...] = h[chunk - 1:]


def rglru_scan_pallas(log_a, x, h0, *, chunk: int = 256, bw: int = 128,
                      interpret: bool):
    """log_a, x: (B, S, W); h0: (B, W). Returns (B, S, W) fp32.

    h0 is passed as (B, 1, W) so its block (1, 1, bw) is lane-aligned and
    spans the full unit middle dim, as the TPU tiling rules require."""
    B, S, W = log_a.shape
    chunk = min(chunk, S)
    bw = min(bw, W)
    assert S % chunk == 0 and W % bw == 0
    nc, nw = S // chunk, W // bw

    kernel = functools.partial(_rglru_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(B, nw, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, bw), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, chunk, bw), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, 1, bw), lambda b, w, c: (b, 0, w)),
        ],
        out_specs=pl.BlockSpec((1, chunk, bw), lambda b, w, c: (b, c, w)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        interpret=interpret,
    )(log_a, x, h0.reshape(B, 1, W))
    return out
