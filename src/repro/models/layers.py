"""Shared neural-net layers: norms, RoPE, dense and grouped expert
projections, GQA attention (train/prefill/decode, causal +
sliding-window), gated short convolutions, MLPs, and parameter
initializers.

All functions are pure; parameters are plain dict pytrees. Attention math is
done in fp32 regardless of the activation dtype.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.kernels.moe_gmm.ops import moe_gmm, plain_ffn
from repro.kernels.wstream_matmul.ops import wstream_matmul
from repro.models import sharding as SH
from repro.models.sharding import constrain


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * w.astype(jnp.float32)).astype(dtype)


def layer_norm(x, w, b, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dtype)


def apply_norm(x, p, kind: str, eps: float):
    if kind == "layernorm":
        return layer_norm(x, p["w"], p["b"], eps)
    return rms_norm(x, p["w"], eps)


def init_norm(key, d, kind: str, dtype):
    if kind == "layernorm":
        return {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}
    return {"w": jnp.ones((d,), dtype)}


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    if theta <= 0:
        return x
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                        # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(positions, d_model: int, dtype):
    """Whisper-style sinusoidal embeddings. positions: (...,)."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / (half - 1))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# ---------------------------------------------------------------------------
# dense projection
# ---------------------------------------------------------------------------

class LayerWeight(NamedTuple):
    """Layer ``layer`` of a stacked projection weight ``stack`` (L, K, N),
    or of an expert stack (L, E, K, N), left unsliced so that ``proj`` or
    ``expert_ffn`` can read it where it lies."""
    stack: jax.Array
    layer: jax.Array

    @property
    def shape(self):
        return self.stack.shape[1:]

    @property
    def dtype(self):
        return self.stack.dtype


_PROJECTIONS = {"attn": ("wq", "wk", "wv", "wo"), "ffn": ("wi", "wg", "wo"),
                "conv": ("w_in", "w_out")}


def layer_params(stacked, i):
    """Layer ``i`` of per-layer params stacked on a leading axis (a dict, or
    a tuple of them): each projection weight (a 3-D stack, or a 4-D stack
    of experts) as a ``LayerWeight``, the rest sliced. Under sharding rules
    all is sliced."""
    def take(tree):
        return jax.tree.map(lambda a: a[i], tree)

    if isinstance(stacked, tuple):
        return tuple(layer_params(s, i) for s in stacked)
    if SH.active():
        return take(stacked)
    out = {}
    for key, sub in stacked.items():
        names = _PROJECTIONS.get(key, ())
        out[key] = {n: LayerWeight(a, i) if n in names and a.ndim in (3, 4)
                    else take(a) for n, a in sub.items()} if names else take(sub)
    return out


def scan_layers(body, carry, xs):
    """``jax.lax.scan(body, carry, (params, rest))`` over layers, for
    inference: ``params`` are stacked per-layer params, and ``body`` gets
    each layer's as ``layer_params`` gives them, so the projections read
    their layer of each weight stack in place instead of a sliced copy."""
    stacked, rest = xs
    n = jax.tree.leaves(stacked)[0].shape[0]
    return jax.lax.scan(
        lambda c, ir: body(c, (layer_params(stacked, ir[0]), ir[1])),
        carry, (jnp.arange(n), rest))


def _weight(w):
    if isinstance(w, LayerWeight):
        return jax.lax.dynamic_index_in_dim(w.stack, w.layer, keepdims=False)
    return w


def proj(x, w, *, transposed: bool = False):
    """``x @ w`` for a dense projection weight ``w`` (K, N) or a
    ``LayerWeight``; with ``transposed``, ``x @ w.T`` for ``w`` (N, K), such
    as an embedding table read as the unembedding.

    Lowered for a TPU, a float32 ``w`` outside any sharding rules goes
    through ``wstream_matmul``: the same default-precision matmul (operands
    rounded to bf16, f32 accumulation) without the bf16 copy of ``w`` that
    XLA writes to HBM on every call, and without a copy of the layer's
    slice of a stack. Every other case, and every other platform, is the
    plain ``x @ w``.
    """
    def plain(x):
        wv = _weight(w)
        return x @ (wv.T if transposed else wv)

    if w.dtype != jnp.float32 or len(w.shape) != 2 or SH.active():
        return plain(x)
    stack, layer = w if isinstance(w, LayerWeight) else (w, 0)
    return jax.lax.platform_dependent(
        x, stack,
        tpu=lambda x, stack: wstream_matmul(x, stack, layer, interpret=False,
                                            transposed=transposed),
        default=lambda x, stack: plain(x))


def expert_ffn(x, wg, wi, wo, offsets):
    """Grouped SwiGLU experts: ``(silu(x[r] @ wg[e]) * (x[r] @ wi[e])) @
    wo[e]`` for the rows ``r`` of expert ``e``, ``[offsets[e], offsets[e +
    1])``, for expert stacks (E, K, F), (E, K, F), (E, F, K) or
    ``LayerWeight``s of them. Rows past ``offsets[E]`` are undefined.

    Lowered for a TPU, float32 weights outside any sharding rules go
    through ``moe_gmm``, which streams each expert's f32 weights through
    VMEM as ``proj`` does; every other case is the plain grouped matmuls.
    """
    if isinstance(wg, LayerWeight):
        layer, stacks = wg.layer, (wg.stack, wi.stack, wo.stack)
    else:
        layer, stacks = 0, (wg[None], wi[None], wo[None])
    if wg.dtype != jnp.float32 or SH.active():
        return plain_ffn(x, *stacks, offsets, layer)
    return jax.lax.platform_dependent(
        x, *stacks, offsets,
        tpu=lambda x, g, i, o, off: moe_gmm(x, g, i, o, off, layer,
                                            interpret=False),
        default=lambda x, g, i, o, off: plain_ffn(x, g, i, o, off, layer))


# ---------------------------------------------------------------------------
# attention cores
# ---------------------------------------------------------------------------

def gqa_attention(q, k, v, *, causal: bool, window: Optional[int] = None,
                  q_pos_offset=0):
    """Full (train/prefill) GQA attention.

    q: (B, Sq, Hq, hd); k, v: (B, Skv, K, hd). Returns (B, Sq, Hq, hd).
    Causal masking uses absolute query position = q_pos_offset + row index.
    """
    B, Sq, Hq, hd = q.shape
    K = k.shape[2]
    G = Hq // K
    qf = q.reshape(B, Sq, K, G, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("bskgh,btkh->bkgst", qf, kf) / math.sqrt(hd)
    qpos = q_pos_offset + jnp.arange(Sq)[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((Sq, k.shape[1]), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, Hq, hd).astype(q.dtype)


def kv_cache_shape(batch: int, seq: int, n_kv_heads: int, head_dim: int):
    """The shape of one attention layer's K or V cache, chosen by
    ``head_dim``: ``(B, S, K, hd)`` where ``hd`` is a multiple of 128, else
    ``(B, S, K * hd)``, so that the minor dimension still fills the TPU's
    128 lanes. A ``(..., K, hd)`` cache with a narrow ``hd`` is stored with
    the sequence minor instead, and the layer step copies it into the
    attention's layout and back. The attention functions read which layout
    a cache has from its rank."""
    if head_dim % 128 == 0:
        return (batch, seq, n_kv_heads, head_dim)
    return (batch, seq, n_kv_heads * head_dim)


def _valid_slots(Sc, pos, window):
    """(B|1, Sc): which cache slots hold a position at or before ``pos``."""
    slots = jnp.arange(Sc)
    posv = jnp.asarray(pos)
    posb = posv if posv.ndim else posv[None]           # (B,) or (1,)
    if window is None:
        return slots[None, :] <= posb[:, None]
    kpos = posb[:, None] - jnp.mod(posb[:, None] - slots[None, :], Sc)
    return kpos >= 0


def _softmax_last(scores):
    # numerically-stable softmax; reduction over a (possibly sharded) Sc dim
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - m)
    s = jnp.sum(e, axis=-1, keepdims=True)
    return e / s


def decode_attention(q, k_cache, v_cache, pos, *, window: Optional[int] = None):
    """Single-token GQA attention over a KV cache.

    q: (B, 1, Hq, hd); caches in either layout of ``kv_cache_shape``:
    (B, Sc, K, hd), or (B, Sc, K * hd), which is read without splitting
    its minor dimension. Sc = max_len (no window) or Sc = window (rotating
    cache). ``pos`` is the current absolute position: a scalar, or a (B,)
    vector for continuous batching (per-slot positions). Keys in a
    rotating cache at slot j hold absolute position
    pos - ((pos - j) mod W); empty slots map to negative positions -> masked.
    """
    if k_cache.ndim == 3:
        return _decode_attention_flat(q, k_cache, v_cache, pos, window)
    B, _, Hq, hd = q.shape
    Sc, K = k_cache.shape[1], k_cache.shape[2]
    G = Hq // K
    qf = q.reshape(B, K, G, hd).astype(jnp.float32)
    scores = jnp.einsum("bkgh,btkh->bkgt", qf, k_cache.astype(jnp.float32))
    scores = scores / math.sqrt(hd)
    valid = _valid_slots(Sc, pos, window)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = _softmax_last(scores)
    out = jnp.einsum("bkgt,btkh->bkgh", probs, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, Hq, hd).astype(q.dtype)


def _decode_attention_flat(q, k_cache, v_cache, pos, window):
    """``decode_attention`` over (B, Sc, K * hd) caches. The query becomes
    block-diagonal, (B, Hq, K * hd) with head (k, g) zero outside kv head
    k's ``hd`` columns, so one contraction over ``K * hd`` gives every
    head's scores; ``probs @ V`` gives (B, Hq, K * hd), of which head (k,
    g) keeps block k. The zeros are exact: each score and output sums the
    same products as the split layout's."""
    B, _, Hq, hd = q.shape
    Sc = k_cache.shape[1]
    K = k_cache.shape[2] // hd
    G = Hq // K
    own = jnp.eye(K, dtype=bool)[None, :, None, :, None]   # (1,K,1,K,1)
    qf = q.reshape(B, K, G, 1, hd).astype(jnp.float32)
    qbd = jnp.where(own, qf, 0.0).reshape(B, Hq, K * hd)
    scores = jnp.einsum("bqc,btc->bqt", qbd, k_cache.astype(jnp.float32))
    scores = scores / math.sqrt(hd)
    valid = _valid_slots(Sc, pos, window)
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    probs = _softmax_last(scores)
    out = jnp.einsum("bqt,btc->bqc", probs, v_cache.astype(jnp.float32))
    out = jnp.where(own, out.reshape(B, K, G, K, hd), 0.0).sum(axis=3)
    return out.reshape(B, 1, Hq, hd).astype(q.dtype)


def dist_decode_attention(q, k_cache, v_cache, k_new, v_new, pos):
    """Decode attention with the KV sequence dim sharded across the mesh
    'kv_seq' axis (flash-decoding across chips, TPU-idiomatic): each shard
    attends over its local KV chunk and the partial (max, sum, weighted-V)
    stats are combined with pmax/psum — bytes on the wire are O(B*H*hd),
    not O(KV). The cache write lands only on the owning shard.

    q, k_new, v_new: (B, 1, Hq|K, hd) replicated over the seq axis;
    caches: (B, Sc, K, hd) or (B, Sc, K * hd), sharded on dim 1. pos:
    scalar. Returns (out (B,1,Hq,hd), k_cache, v_cache), the caches in the
    layout they came in.
    """
    if k_cache.ndim == 3:
        split = k_new.shape[:1] + k_cache.shape[1:2] + k_new.shape[2:]
        out, kc, vc = dist_decode_attention(
            q, k_cache.reshape(split), v_cache.reshape(split), k_new, v_new,
            pos)
        return out, kc.reshape(k_cache.shape), vc.reshape(v_cache.shape)
    mesh = SH.mesh()
    seq_ax = SH.rule("kv_seq")
    batch_ax = SH.rule("kv_batch")
    B, _, Hq, hd = q.shape
    K = k_cache.shape[2]
    G = Hq // K
    n = mesh.shape[seq_ax]
    chunk = k_cache.shape[1] // n

    def body(qb, kc, vc, kn, vn):
        i = jax.lax.axis_index(seq_ax)
        off = i * chunk
        slot = pos - off
        ok = (slot >= 0) & (slot < chunk)
        idx = jnp.clip(slot, 0, chunk - 1)
        kc = kc.at[:, idx].set(jnp.where(ok, kn[:, 0], kc[:, idx]))
        vc = vc.at[:, idx].set(jnp.where(ok, vn[:, 0], vc[:, idx]))
        qf = qb.reshape(-1, K, G, hd).astype(jnp.float32)
        s = jnp.einsum("bkgh,btkh->bkgt", qf, kc.astype(jnp.float32))
        s = s / math.sqrt(hd)
        kpos = off + jnp.arange(chunk)
        s = jnp.where((kpos <= pos)[None, None, None, :], s, -1e30)
        m_loc = jnp.max(s, axis=-1)
        m = jax.lax.pmax(m_loc, seq_ax)                    # (b,K,G)
        e = jnp.exp(s - m[..., None])
        l = jax.lax.psum(jnp.sum(e, axis=-1), seq_ax)      # (b,K,G)
        o = jnp.einsum("bkgt,btkh->bkgh", e, vc.astype(jnp.float32))
        o = jax.lax.psum(o, seq_ax) / l[..., None]
        out = o.reshape(-1, 1, Hq, hd).astype(qb.dtype)
        return out, kc, vc

    def bspec(*rest):
        return P(batch_ax, *rest)
    out, kc, vc = shard_map(
        body, mesh=mesh,
        in_specs=(bspec(None, None, None), bspec(seq_ax, None, None),
                  bspec(seq_ax, None, None), bspec(None, None, None),
                  bspec(None, None, None)),
        out_specs=(bspec(None, None, None), bspec(seq_ax, None, None),
                   bspec(seq_ax, None, None)),
    )(q, k_cache, v_cache, k_new, v_new)
    return out, kc, vc


def _as_cache_rows(cache, kv):
    """``kv`` (B, S, K, hd) in ``cache``'s layout: (B, S, K * hd) for a
    rank-3 cache, else unchanged."""
    if cache.ndim == 3:
        return kv.reshape(*kv.shape[:2], cache.shape[2])
    return kv


def cache_update_decode(cache, new, pos, *, window: Optional[int] = None):
    """Write one token's k or v (B, 1, K, hd) into the cache at ``pos``
    (scalar, or (B,) per-slot positions for continuous batching). A
    (B, Sc, K * hd) cache (``kv_cache_shape``) takes one ``K * hd`` row
    per slot, a (B, Sc, K, hd) cache the token as it is."""
    new = _as_cache_rows(cache, new)
    posv = jnp.asarray(pos)
    slot = posv if window is None else jnp.mod(posv, cache.shape[1])
    if posv.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(
            cache, new.astype(cache.dtype), slot, axis=1)
    B = cache.shape[0]
    return cache.at[jnp.arange(B), slot].set(new[:, 0].astype(cache.dtype))


def cache_fill_prefill(cache, k, *, window: Optional[int] = None):
    """Write a full prompt's keys/values (B, S, K, hd) into a fresh cache
    of either layout of ``kv_cache_shape``."""
    k = _as_cache_rows(cache, k)
    S, Sc = k.shape[1], cache.shape[1]
    if window is None or S <= Sc:
        if S > Sc:
            k = k[:, -Sc:]
            S = Sc
        return jax.lax.dynamic_update_slice_in_dim(cache, k.astype(cache.dtype), 0, axis=1)
    # rotating: keep last Sc tokens, token at abs pos p lands in slot p % Sc
    tail = k[:, -Sc:]                                  # positions [S-Sc, S)
    pos0 = S - Sc
    slots = jnp.mod(pos0 + jnp.arange(Sc), Sc)
    return cache.at[:, slots].set(tail.astype(cache.dtype))


# ---------------------------------------------------------------------------
# attention block (pre-norm residual)
# ---------------------------------------------------------------------------

def init_attention(key, cfg, dtype, cross: bool = False):
    D, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (D, qd), dtype),
        "wk": dense_init(ks[1], (D, kvd), dtype),
        "wv": dense_init(ks[2], (D, kvd), dtype),
        "wo": dense_init(ks[3], (qd, D), dtype, scale=1.0 / math.sqrt(qd)),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((qd,), dtype)
        p["bk"] = jnp.zeros((kvd,), dtype)
        p["bv"] = jnp.zeros((kvd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), dtype)
        p["k_norm"] = jnp.ones((cfg.head_dim,), dtype)
    return p


def _project_qkv(p, x, cfg, positions, rope: bool):
    B = x.shape[0]
    S = x.shape[1]
    q = proj(x, p["wq"])
    k = proj(x, p["wk"])
    v = proj(x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope and cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    return q, k, v


def attn_forward(p, x, cfg, *, window=None, causal=True):
    """Full-sequence attention (train / prefill without cache)."""
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(p, x, cfg, positions, rope=True)
    out = gqa_attention(q, k, v, causal=causal, window=window)
    out = constrain(out, "batch", None, "heads", None)
    if _tp_axis_ok(cfg.n_heads, "heads"):
        return tp_attn_out(out, p["wo"], cfg)
    return proj(out.reshape(x.shape[0], S, cfg.q_dim), p["wo"])


def attn_prefill(p, x, cfg, k_cache, v_cache, *, window=None):
    """Prefill: full attention + fill the cache. Returns (out, k_cache, v_cache)."""
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(p, x, cfg, positions, rope=True)
    out = gqa_attention(q, k, v, causal=True, window=window)
    k_cache = cache_fill_prefill(k_cache, k, window=window)
    v_cache = cache_fill_prefill(v_cache, v, window=window)
    out = proj(out.reshape(x.shape[0], S, cfg.q_dim), p["wo"])
    return out, k_cache, v_cache


def attn_decode(p, x, cfg, k_cache, v_cache, pos, *, window=None):
    """Decode one token. x: (B, 1, D); pos scalar or (B,).
    Returns (out, k_cache, v_cache)."""
    posv = jnp.asarray(pos)
    if posv.ndim == 0:
        positions = jnp.full((x.shape[0], 1), posv)
    else:
        positions = posv[:, None]
    q, k, v = _project_qkv(p, x, cfg, positions, rope=True)
    if SH.rule("kv_seq") is not None and window is None and posv.ndim == 0:
        # seq-sharded KV: explicit flash-decoding across chips
        out, k_cache, v_cache = dist_decode_attention(q, k_cache, v_cache,
                                                      k, v, pos)
        out = out.reshape(x.shape[0], 1, cfg.q_dim) @ p["wo"]
        return out, k_cache, v_cache
    k_cache = cache_update_decode(k_cache, k, pos, window=window)
    v_cache = cache_update_decode(v_cache, v, pos, window=window)
    rest = (None,) * (k_cache.ndim - 2)
    k_cache = constrain(k_cache, "kv_batch", "kv_seq", *rest)
    v_cache = constrain(v_cache, "kv_batch", "kv_seq", *rest)
    out = decode_attention(q, k_cache, v_cache, pos, window=window)
    out = proj(out.reshape(x.shape[0], 1, cfg.q_dim), p["wo"])
    return out, k_cache, v_cache


def cross_attn_cache(p, enc_out, cfg):
    """Project encoder output to cross-attention K/V once (at prefill)."""
    B, S, _ = enc_out.shape
    k = (enc_out @ p["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = (enc_out @ p["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    return k, v


def cross_attn_apply(p, x, cfg, k, v):
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    out = gqa_attention(q, k, v, causal=False)
    return out.reshape(B, S, cfg.q_dim) @ p["wo"]


# ---------------------------------------------------------------------------
# gated short convolution (LFM2)
# ---------------------------------------------------------------------------

def init_conv(key, cfg, dtype):
    D, W = cfg.d_model, cfg.conv_width
    ks = jax.random.split(key, 3)
    return {"w_in": dense_init(ks[0], (D, 3 * D), dtype),
            "w_conv": dense_init(ks[1], (W, D), dtype,
                                 scale=1.0 / math.sqrt(W)),
            "w_out": dense_init(ks[2], (D, D), dtype)}


def _conv_mix(p, x, z_before):
    """The block's output for ``x`` (B, S, D), given the ``W - 1`` values
    of z before it (B, W - 1, D); also the last ``W - 1`` values of z."""
    b, c, u = jnp.split(proj(x, p["w_in"]), 3, axis=-1)
    z = jnp.concatenate([z_before.astype(x.dtype), b * u], axis=1)
    w = p["w_conv"].astype(x.dtype)
    W, S = w.shape[0], x.shape[1]
    y = sum(w[j] * z[:, j:j + S] for j in range(W))
    return proj(c * y, p["w_out"]), z[:, S:]


def conv_prefill(p, x, cfg):
    """Gated short convolution over a whole sequence ``x`` (B, S, D):
    ``[b, c, u] = x @ W_in``, ``z = b * u``, ``y_t = sum_j w[j] *
    z_{t-W+1+j}`` (z is 0 before the first position), out ``(c * y) @
    W_out``. Returns (out, state): the state is the last ``W - 1`` values
    of z (B, W - 1, D), zeros where the sequence is shorter."""
    B, _, D = x.shape
    return _conv_mix(p, x, jnp.zeros((B, cfg.conv_width - 1, D), x.dtype))


def conv_decode(p, x, state):
    """One token ``x`` (B, 1, D) from the state ``conv_prefill`` or the
    last step left; returns (out, the state rolled by one)."""
    out, z = _conv_mix(p, x, state)
    return out, z.astype(state.dtype)


# ---------------------------------------------------------------------------
# explicit-TP projections (bf16 all-reduce)
# ---------------------------------------------------------------------------
# XLA all-reduces the f32 matmul accumulator of a sharded contraction before
# converting to bf16 — doubling TP collective bytes. These shard_map variants
# convert the local partial product to bf16 *before* the psum, halving the
# wire bytes (standard TP trade: one bf16 rounding on the partial sums).
# Enabled by the 'tp_bf16_ar' rule; autodiff through shard_map keeps the
# backward psums in bf16 too.

def _tp_axis_ok(dim: int, axis_name: str = "d_ff") -> bool:
    ax = SH.rule(axis_name)
    m = SH.mesh()
    return bool(SH.rule("tp_bf16_ar") and ax is not None and m is not None
                and dim % m.shape[ax] == 0)


def tp_mlp_forward(p, x, cfg):
    """SwiGLU/GeLU FFN with explicit TP over the d_ff axis and bf16 psum."""
    ax = SH.rule("d_ff")
    mesh = SH.mesh()
    batch_ax = SH.rule("batch")

    def body(xl, *ws):
        if len(ws) == 3:
            wi, wg, wo = ws
            h = jax.nn.silu(xl @ wg) * (xl @ wi)
        else:
            wi, wo = ws
            h = jax.nn.gelu(xl @ wi)
        # bf16-native dot so the psum operand is born bf16 (no convert for
        # XLA's excess-precision pass to hoist past the collective)
        y = jax.lax.dot_general(h, wo, (((h.ndim - 1,), (0,)), ((), ())),
                                preferred_element_type=xl.dtype)
        return jax.lax.psum(y, ax)

    ws = (p["wi"], p["wg"], p["wo"]) if "wg" in p else (p["wi"], p["wo"])
    in_specs = [P(batch_ax, None, None)]
    for w in ws[:-1]:
        in_specs.append(P(None, ax))
    in_specs.append(P(ax, None))
    return shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=P(batch_ax, None, None))(x, *ws)


def tp_attn_out(out_heads, wo, cfg):
    """Attention output projection (B,S,Hq,hd)@(Hq*hd,D) with heads sharded
    over the model axis and a bf16 psum."""
    ax = SH.rule("heads")
    mesh = SH.mesh()
    batch_ax = SH.rule("batch")
    n = mesh.shape[ax]
    hd = cfg.head_dim

    def body(ol, wl):
        B, S, hl, _ = ol.shape
        y = (ol.reshape(B, S, hl * hd) @ wl).astype(ol.dtype)
        return jax.lax.psum(y, ax)

    del n
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_ax, None, ax, None), P(ax, None)),
        out_specs=P(batch_ax, None, None),
    )(out_heads, wo)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, cfg, dtype, d_ff: Optional[int] = None):
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp == "swiglu":
        return {
            "wi": dense_init(ks[0], (D, F), dtype),
            "wg": dense_init(ks[1], (D, F), dtype),
            "wo": dense_init(ks[2], (F, D), dtype, scale=1.0 / math.sqrt(F)),
        }
    return {
        "wi": dense_init(ks[0], (D, F), dtype),
        "wo": dense_init(ks[2], (F, D), dtype, scale=1.0 / math.sqrt(F)),
    }


def mlp_forward(p, x, cfg):
    if _tp_axis_ok(p["wi"].shape[-1]):
        return tp_mlp_forward(p, x, cfg)
    if "wg" in p:
        h = jax.nn.silu(proj(x, p["wg"])) * proj(x, p["wi"])
    else:
        h = jax.nn.gelu(proj(x, p["wi"]))
    h = constrain(h, "batch", None, "d_ff")
    return proj(h, p["wo"])
