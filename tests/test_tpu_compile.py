"""Compile-only checks for one TPU v5e chip, against a described v5e:2x2
topology: nothing runs and no chip is needed, only the TPU compiler.

- The engine's jitted prefill and decode steps (``serving.engine.
  build_steps``) at granite_3_8b's published widths, cut to
  ``CHIP_LAYERS`` layers, under the one-chip traffic (``Traffic()``), must
  compile and leave at least 2 GB of the chip's 16 GiB free; so must
  lfm2_24b_a2b's, cut to ``LFM2_LAYERS`` layers with 16 of its 64 experts
  held, under its benchmark cell's traffic (``LFM2_TRAFFIC``).
- Each Pallas kernel must be accepted by the TPU compiler at a real width.
- Both steps must read every f32 projection weight in place through
  ``wstream_matmul``: no bf16 copy of a weight, no copy of a layer's slice;
  lfm2_24b_a2b's steps must hold no large f32 -> bf16 convert either (its
  experts go through ``moe_gmm``).
- Neither model's steps may copy or transpose an attention layer's K or V
  cache: the cache is stored in the layout the attention reads.

The topology is described in a fixture, never at import: only one process
at a time may load the TPU library.
"""
import collections
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.mlstm_chunk.ops import mlstm_chunk
from repro.kernels.moe_gmm.ops import moe_gmm
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.wstream_matmul.ops import wstream_matmul
from repro.launch.serve import (CHIP_LAYERS, EXPERT_SHARDS, Traffic,
                                cut_depth, cut_experts)
from repro.models import LM
from repro.serving.engine import build_steps

HBM_BYTES = 16 * 2**30          # one TPU v5e
HEADROOM_BYTES = 2 * 10**9
# lfm2_24b_a2b's cut and its benchmark cell's serving: 32 slots live, the
# KV ring's 32 places full, prompts up to 1536 tokens
LFM2_LAYERS = 14
LFM2_TRAFFIC = Traffic(requests=64, prompt_len=1536, decode_slots=32,
                       max_len=2048)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    cc.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _nbytes(tree):
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def compiled_steps(sharding, n_layers: int, traffic: Traffic,
                   arch: str = "granite_3_8b"):
    """Compile the engine's steps for ``arch`` cut to ``n_layers`` (and
    one expert shard, as ``launch/serve.py --layers`` serves it):
    ``(params, cache1, dcache, compiled prefill, compiled decode)``, the
    first three as shapes."""
    cfg = cut_experts(cut_depth(get_config(arch), n_layers), EXPERT_SHARDS)
    lm = LM(cfg)
    prefill, decode = build_steps(lm)
    params = _on(sharding, jax.eval_shape(
        lambda k: lm.init(k, jnp.float32), jax.random.key(0)))
    cache1 = _on(sharding, jax.eval_shape(
        lambda: lm.init_cache(1, traffic.max_len, dtype=jnp.float32)))
    dcache = jax.eval_shape(
        lambda: lm.init_cache(traffic.decode_slots, traffic.max_len,
                              dtype=jnp.float32))
    dcache["pos"] = jax.ShapeDtypeStruct((traffic.decode_slots,), jnp.int32)
    dcache = _on(sharding, dcache)
    toks = jax.ShapeDtypeStruct((1, traffic.prompt_len), jnp.int32,
                                sharding=sharding)
    tok = jax.ShapeDtypeStruct((traffic.decode_slots,), jnp.int32,
                               sharding=sharding)
    return (params, cache1, dcache,
            prefill.lower(params, toks, cache1).compile(),
            decode.lower(params, tok, dcache).compile())


def step_bytes(cache1, dcache, pre, dec, traffic: Traffic) -> dict:
    """The device bytes live at the peak of each phase of compiled
    steps."""
    pre, dec = pre.memory_analysis(), dec.memory_analysis()
    # prefilled caches wait in the KV ring while every decode slot is busy
    ring = max(traffic.requests - traffic.decode_slots, 0) * _nbytes(cache1)

    def total(m):
        return (m.argument_size_in_bytes + m.output_size_in_bytes +
                m.temp_size_in_bytes)
    return {"decode": total(dec) + ring,
            # the decode worker's cache stays resident while prefill runs
            "prefill": total(pre) + _nbytes(dcache) + ring}


def test_engine_steps_fit_one_chip(one_chip):
    _, cache1, dcache, pre, dec = compiled_steps(one_chip, CHIP_LAYERS,
                                                 Traffic())
    peak = step_bytes(cache1, dcache, pre, dec, Traffic())
    for phase, nbytes in peak.items():
        assert nbytes <= HBM_BYTES - HEADROOM_BYTES, (phase, nbytes)


# ``%name = bf16[2048,1536]{...} convert(`` anywhere in the compiled text
_CONVERT = re.compile(r"= bf16\[([\d,]*)\]\S* convert\(")


def _weight_shapes(params) -> set:
    """The shapes of every weight of a million elements or more: a whole
    stack, a layer of it, an expert of it, either orientation."""
    out = set()
    for leaf in jax.tree.leaves(params):
        if leaf.size < 2**20:
            continue
        for shape in (leaf.shape, leaf.shape[1:], leaf.shape[2:]):
            if len(shape) >= 2:
                out |= {shape, (*shape[:-2], shape[-1], shape[-2])}
    return out


def test_lfm2_steps_fit_one_chip_without_weight_converts(one_chip):
    """lfm2_24b_a2b at its cut: both steps fit one chip with 2 GB free, and
    neither rounds a weight (a million elements or more: a projection, an
    expert stack, the tied embedding) from f32 to bf16 in HBM."""
    params, cache1, dcache, pre, dec = compiled_steps(
        one_chip, LFM2_LAYERS, LFM2_TRAFFIC, arch="lfm2_24b_a2b")
    peak = step_bytes(cache1, dcache, pre, dec, LFM2_TRAFFIC)
    for phase, nbytes in peak.items():
        assert nbytes <= HBM_BYTES - HEADROOM_BYTES, (phase, nbytes)
    weights = _weight_shapes(params)
    for step in (pre, dec):
        text = step.as_text()
        assert "moe_gmm" in text and "wstream_matmul" in text
        for dims in _CONVERT.findall(text):
            shape = tuple(int(d) for d in dims.split(",") if d)
            while shape[:1] == (1,):
                shape = shape[1:]
            assert shape not in weights, shape


# Real widths: decode attention at granite_3_8b (K=8, hd=128, S=2048),
# flash attention at granite_3_8b (32/8 heads x 128), the RG-LRU scan at
# recurrentgemma_2b (W=2560), the mLSTM chunk at xlstm (hd 512, S=2048),
# the weight-streaming matmul at granite_3_8b's MLP (decode rows) and
# unembedding (prefill rows, vocabulary 49155), the grouped expert FFN at
# lfm2_24b_a2b's 16 held experts (decode: 32 slots x top-4 rows; prefill:
# 1536 tokens x top-4).
KERNEL_CASES = {
    "decode_attention": (decode_attention, [
        ((8, 32, 128), jnp.float32), ((8, 2048, 8, 128), jnp.float32),
        ((8, 2048, 8, 128), jnp.float32), ((), jnp.int32)]),
    "flash_attention": (flash_attention, [
        ((1, 2048, 32, 128), jnp.float32), ((1, 2048, 8, 128), jnp.float32),
        ((1, 2048, 8, 128), jnp.float32)]),
    "rglru_scan": (rglru_scan, [
        ((2, 2048, 2560), jnp.float32), ((2, 2048, 2560), jnp.float32),
        ((2, 2560), jnp.float32)]),
    "wstream_matmul": (wstream_matmul, [
        ((8, 4096), jnp.float32), ((4096, 12800), jnp.float32)]),
    "wstream_matmul_ragged": (wstream_matmul, [
        ((768, 4096), jnp.float32), ((4096, 49155), jnp.float32)]),
    "moe_gmm": (moe_gmm, [
        ((128, 2048), jnp.float32), ((16, 2048, 1536), jnp.float32),
        ((16, 2048, 1536), jnp.float32), ((16, 1536, 2048), jnp.float32),
        ((17,), jnp.int32)]),
    "moe_gmm_prefill": (moe_gmm, [
        ((6144, 2048), jnp.float32), ((16, 2048, 1536), jnp.float32),
        ((16, 2048, 1536), jnp.float32), ((16, 1536, 2048), jnp.float32),
        ((17,), jnp.int32)]),
    "mlstm_chunk": (mlstm_chunk, [
        ((8, 2048, 512), jnp.float32), ((8, 2048, 512), jnp.float32),
        ((8, 2048, 512), jnp.float32), ((8, 2048), jnp.float32),
        ((8, 2048), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_kernel_compiles_for_tpu(one_chip, name):
    fn, shapes = KERNEL_CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ``%name = f32[4096,12800]{1,0:T(8,128)} opcode(`` in the compiled text
_INSTR = re.compile(r"%\S+ = (f32|bf16)\[([\d,]*)\]\S* ([\w-]+)\(")
# the weight operand of a kernel call, the last of its operand layouts
_KERNEL_W = re.compile(r'custom_call_target="tpu_custom_call", '
                       r'operand_layout_constraints=\{.*f32\[([\d,]+)\]'
                       r'\{[^}]*\}\}')


def test_steps_stream_weights_in_place(one_chip):
    """Every weight of ``jit_decode`` and ``jit_prefill`` is read where it
    lies: an array of a weight's shape (a layer, a stack, either
    orientation, f32 or bf16) comes only from a parameter, a loop-state
    element or a bitcast, so no weight is converted to bf16 and no layer's
    slice is copied. One ``wstream_matmul`` call reads each of the layer
    body's seven projection stacks, and one the unembedding."""
    params, *_, pre, dec = compiled_steps(one_chip, CHIP_LAYERS, Traffic())
    weights = set()
    for leaf in jax.tree.leaves(params):
        for shape in (leaf.shape, leaf.shape[1:]):
            if len(shape) >= 2 and leaf.size >= 2**20:
                weights.add(shape)
                weights.add((*shape[:-2], shape[-1], shape[-2]))
    stacks = collections.Counter(
        tuple(leaf.shape) for part in ("attn", "ffn")
        for slot in params["slots"] for leaf in slot[part].values())
    assert sum(stacks.values()) == 7
    K, N = params["unembed"].shape
    for step in (pre, dec):
        text = step.as_text()
        for dtype, dims, op in _INSTR.findall(text):
            shape = tuple(int(d) for d in dims.split(",") if d)
            while shape[:1] == (1,):
                shape = shape[1:]
            assert shape not in weights or op in (
                "parameter", "get-tuple-element", "bitcast"), (
                dtype, shape, op)
        read = collections.Counter(
            tuple(int(d) for d in m.split(","))
            for m in _KERNEL_W.findall(text))
        unembed = read.pop((1, K, N), 0) + read.pop((1, N, K), 0)
        assert unembed == 1 and read == stacks, (read, stacks)


_MOVE = re.compile(r"%\S+ = f32\[([\d,]*)\]\S* (?:copy|transpose)\(")


@pytest.mark.parametrize("arch", ["granite_3_8b", "lfm2_24b_a2b"])
def test_steps_leave_kv_cache_in_place(one_chip, arch):
    """No f32 array with as many elements as one attention layer's K or V
    cache comes from a ``copy`` or a ``transpose`` in ``jit_prefill`` or
    ``jit_decode``: at head_dim 64 (lfm2_24b_a2b) as at 128 (granite), the
    cache is stored in the layout that the attention and the cache update
    read and write, so no layer step moves it out and back."""
    n_layers, traffic = ((LFM2_LAYERS, LFM2_TRAFFIC) if arch == "lfm2_24b_a2b"
                         else (CHIP_LAYERS, Traffic()))
    _, cache1, dcache, pre, dec = compiled_steps(one_chip, n_layers, traffic,
                                                 arch=arch)
    for cache, step in ((cache1, pre), (dcache, dec)):
        kv = {leaf.size // leaf.shape[0]
              for slot in cache["slots"] for name, leaf in slot.items()
              if name in ("k", "v")}
        assert kv
        moved = [dims for dims in _MOVE.findall(step.as_text())
                 if math.prod(int(d) for d in dims.split(",") if d) in kv]
        assert not moved, moved
