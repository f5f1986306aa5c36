"""Pallas TPU matmul that streams an f32 weight through VMEM.

``y = x @ w`` at the TPU's default matmul precision: both operands rounded
to nearest-even bfloat16, products accumulated in float32. XLA rounds an
f32 weight with a standalone ``convert`` that writes a bf16 copy of the
whole weight to HBM on every call, which the dot then reads again: 6 + 2
bytes moved per parameter. Here each weight tile is read from HBM once,
at 4 bytes a parameter, and rounded in VMEM on its way to the MXU.

The weight is one layer of a stack ``(L, K, N)``, picked by a prefetched
scalar, so a layer loop hands the kernel the stack itself and no slice of
it is copied. A stack stored transposed, ``(L, N, K)``, is read as it is.

The grid runs over M tiles, N tiles and K tiles (the reduction, minor).
Up to ``tm`` rows of ``x`` are in each block, so for M up to ``tm`` the
weight is read exactly once per call; the f32 output block stays resident
across the K steps and is the accumulator. A ragged last M or N tile is
padded by the pipeline: its extra rows and columns reach only output
elements that are never written back. K tiles always divide K.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


def _kernel(layer_ref, x_ref, w_ref, o_ref, *, transposed: bool):
    del layer_ref                                   # used by the index maps

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.bfloat16)
    w = w_ref[...].astype(jnp.bfloat16)
    contract = ((1,), (1,)) if transposed else ((1,), (0,))
    o_ref[...] += jax.lax.dot_general(x, w, (contract, ((), ())),
                                      preferred_element_type=jnp.float32)


def _tile(dim: int, cap: int, *, exact: bool) -> int:
    """Largest multiple of 128 up to ``cap`` that divides ``dim``; else
    ``dim`` itself where it fits under ``cap`` or must be whole
    (``exact``), else ``cap``, which leaves a ragged last tile."""
    for t in range(min(cap, dim) // LANE * LANE, 0, -LANE):
        if dim % t == 0:
            return t
    return dim if exact or dim <= cap else cap


def wstream_matmul_pallas(x, w, layer, *, transposed: bool = False,
                          tm: int = 1024, tk: int = 512, tn: int = 2048,
                          interpret: bool):
    """x: (M, K) float; w: (L, K, N) float32, or (L, N, K) if
    ``transposed``; layer: int32 scalar. Returns x @ w[layer], (M, N)
    float32."""
    M, K = x.shape
    N = w.shape[1] if transposed else w.shape[2]
    tm = M if M <= tm else tm
    tk = _tile(K, tk, exact=True)
    # keep the resident f32 output block near 4 MiB at large M
    tn = _tile(N, max(LANE, min(tn, (1 << 20) // tm // LANE * LANE)),
               exact=False)
    grid = (pl.cdiv(M, tm), pl.cdiv(N, tn), K // tk)
    if transposed:
        w_spec = pl.BlockSpec((None, tn, tk), lambda m, n, k, li: (li[0], n, k))
    else:
        w_spec = pl.BlockSpec((None, tk, tn), lambda m, n, k, li: (li[0], k, n))
    return pl.pallas_call(
        functools.partial(_kernel, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec((tm, tk), lambda m, n, k, li: (m, k)),
                      w_spec],
            out_specs=pl.BlockSpec((tm, tn), lambda m, n, k, li: (m, n))),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * K * N, transcendentals=0,
            bytes_accessed=4 * (K * N * grid[0] + M * K * grid[1] + M * N)),
        name="wstream_matmul",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), x, w)
