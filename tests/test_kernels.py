"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
interpret=True (the kernel body runs in Python on CPU)."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_scan_ref

KEY = jax.random.key(0)


@pytest.mark.parametrize("B,S,Hq,K,hd,window", [
    (2, 256, 4, 2, 64, None),
    (1, 128, 2, 2, 128, None),
    (2, 256, 4, 4, 64, 64),
    (1, 512, 8, 2, 64, None),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, S, Hq, K, hd, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, K, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, K, hd), dtype)
    out = flash_attention(q, k, v, window=window, interpret=True)
    kk = jnp.repeat(k, Hq // K, 2)
    vv = jnp.repeat(v, Hq // K, 2)
    ref = flash_attention_ref(q.astype(jnp.float32), kk.astype(jnp.float32),
                              vv.astype(jnp.float32), window=window)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) < tol


@pytest.mark.parametrize("B,S,Hq,K,hd,bs,pos", [
    (2, 1024, 8, 2, 64, 256, 700),
    (1, 512, 4, 4, 128, 128, 511),
    (3, 512, 16, 2, 64, 512, 100),
    (2, 256, 8, 8, 64, 64, 0),
])
def test_decode_attention(B, S, Hq, K, hd, bs, pos):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hq, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
    out = decode_attention(q, kc, vc, pos, bs=bs, interpret=True)
    ref = decode_attention_ref(q, kc, vc, pos)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


@pytest.mark.parametrize("B,S,W,chunk,bw", [
    (2, 512, 256, 128, 128),
    (1, 256, 128, 256, 64),
    (3, 1024, 384, 64, 128),
    (2, 128, 256, 32, 256),
])
def test_rglru_scan(B, S, W, chunk, bw):
    ks = jax.random.split(KEY, 3)
    la = -jnp.abs(jax.random.normal(ks[0], (B, S, W))) * 0.2
    x = jax.random.normal(ks[1], (B, S, W))
    h0 = jax.random.normal(ks[2], (B, W))
    out = rglru_scan(la, x, h0, chunk=chunk, bw=bw, interpret=True)
    ref = rglru_scan_ref(la, x, h0)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-4


@pytest.mark.parametrize("B,S,hd,chunk", [
    (2, 256, 64, 128), (1, 128, 32, 32), (3, 256, 128, 256),
])
def test_mlstm_chunk(B, S, hd, chunk):
    from repro.kernels.mlstm_chunk.ops import mlstm_chunk
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, S, hd))
    k = jax.random.normal(ks[1], (B, S, hd)) / jnp.sqrt(hd)
    v = jax.random.normal(ks[2], (B, S, hd))
    li = jax.random.normal(ks[3], (B, S)) * 0.5
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, S)) + 3.0)
    out = mlstm_chunk(q, k, v, li, lf, chunk=chunk, interpret=True)
    ref = mlstm_chunk(q, k, v, li, lf, impl="ref", interpret=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-4
