"""Pallas TPU grouped expert FFN over rows sorted by expert, streaming each
expert's f32 weights through VMEM.

``y[r] = (silu(x[r] @ wg[e]) * (x[r] @ wi[e])) @ wo[e]`` for every row
``r`` in ``[offsets[e], offsets[e + 1])``: the gate, up and down matmuls
of a SwiGLU expert in one call, each at the TPU's default matmul
precision (operands rounded to nearest-even bfloat16 in VMEM, products
accumulated in float32; the hidden activation is rounded to bfloat16 on
its way to the down matmul, as XLA rounds it). The weights are one layer
of expert stacks ``(L, E, K, F)``/``(L, E, F, K)``, picked by a
prefetched scalar, so a layer loop hands the kernel the stacks themselves
and neither a slice nor a bf16 copy of any expert is written to HBM.

The rows are cut into tiles of ``tm``. A work item is one (expert, row
tile) pair whose rows meet; an expert with no rows still gets one item.
The grid runs over a fixed number of items, ``m_tiles + E - 1``, which
bounds the real ones, and over tiles of the hidden width F (the
reduction of the down matmul, minor). The items past the real ones repeat
the last and point at the blocks it ended on, so they fetch nothing and
compute nothing. K is whole in every block. Where every row lies in one
tile (a decode step), each expert's weights are read from HBM exactly
once per call. Items of one tile are consecutive, so its output block
stays resident and accumulates each expert's rows under a mask. Rows at
or past ``offsets[E]`` in a tile that no item visits are not written:
they are undefined.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(layer_ref, off_ref, expert_ref, tile_ref, first_ref, valid_ref,
            x_ref, wg_ref, wi_ref, wo_ref, o_ref, *, tm: int):
    del layer_ref                                   # used by the index maps
    i, f = pl.program_id(0), pl.program_id(1)

    @pl.when((first_ref[i] == 1) & (f == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(valid_ref[i] == 1)
    def _accumulate():
        e = expert_ref[i]
        rows = tile_ref[i] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (rows >= off_ref[e]) & (rows < off_ref[e + 1])
        x = x_ref[...].astype(jnp.bfloat16)

        def mm(a, w_ref):
            return jnp.dot(a, w_ref[...].astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
        a = mm(x, wg_ref)
        h = a * jax.nn.sigmoid(a) * mm(x, wi_ref)
        o_ref[...] += jnp.where(mine, mm(h.astype(jnp.bfloat16), wo_ref),
                                0.0)


def work_items(offsets, tm: int, m_tiles: int):
    """The grid's work items for group ``offsets`` (E + 1,): per item its
    expert, its row tile, whether it is the first item of its tile, and
    whether it is real. ``m_tiles + E - 1`` items, int32 each."""
    E = offsets.shape[0] - 1
    n_items = m_tiles + E - 1
    start, end = offsets[:-1], offsets[1:]
    first = jnp.minimum(start // tm, m_tiles - 1)
    last = jnp.maximum(first, (end - 1) // tm)
    count = last - first + 1
    expert = jnp.repeat(jnp.arange(E, dtype=jnp.int32), count,
                        total_repeat_length=n_items)
    idx = jnp.arange(n_items, dtype=jnp.int32)
    valid = idx < jnp.sum(count)
    begin = jnp.cumsum(count) - count
    tile = jnp.where(valid, first[expert] + idx - begin[expert], last[E - 1])
    fresh = jnp.concatenate([jnp.ones((1,), bool), tile[1:] != tile[:-1]])
    as_i32 = lambda a: a.astype(jnp.int32)  # noqa: E731
    return as_i32(expert), as_i32(tile), as_i32(fresh), as_i32(valid)


def moe_gmm_pallas(x, wg, wi, wo, offsets, layer, *, tm: int, tf: int,
                   interpret: bool):
    """x: (M, K) float with M a multiple of ``tm`` (or M == tm); wg, wi:
    (L, E, K, F) float32; wo: (L, E, F, K) float32; offsets: (E + 1,)
    int32, non-decreasing, from 0; layer: int32 scalar. Returns (M, K)
    float32: each expert's rows through its FFN, undefined past
    ``offsets[E]`` (module docstring)."""
    M, K = x.shape
    _, E, _, F = wg.shape
    m_tiles, f_tiles = M // tm, F // tf
    grid = (m_tiles + E - 1, f_tiles)
    expert, tile, first, valid = work_items(offsets, tm, m_tiles)

    def f_of(i, f, va):
        """The hidden tile an item reads: padding stays on the last."""
        return jnp.where(va[i] == 1, f, f_tiles - 1)

    def up_map(i, f, li, off, ex, ti, fi, va):
        return li[0], ex[i], 0, f_of(i, f, va)

    def down_map(i, f, li, off, ex, ti, fi, va):
        return li[0], ex[i], f_of(i, f, va), 0

    def rows_map(i, f, li, off, ex, ti, fi, va):
        return ti[i], 0

    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=grid,
            in_specs=[pl.BlockSpec((tm, K), rows_map),
                      pl.BlockSpec((None, None, K, tf), up_map),
                      pl.BlockSpec((None, None, K, tf), up_map),
                      pl.BlockSpec((None, None, tf, K), down_map)],
            out_specs=pl.BlockSpec((tm, K), rows_map)),
        out_shape=jax.ShapeDtypeStruct((M, K), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        cost_estimate=pl.CostEstimate(
            flops=6 * tm * K * F * grid[0], transcendentals=tm * F * grid[0],
            bytes_accessed=4 * (3 * E * K * F + 2 * M * K)),
        name="moe_gmm",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), offsets.astype(jnp.int32),
      expert, tile, first, valid, x, wg, wi, wo)
