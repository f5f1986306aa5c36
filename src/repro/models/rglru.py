"""Griffin-style hybrid family (RecurrentGemma): RG-LRU recurrent blocks +
local (sliding-window) attention, pattern ("rec","rec","attn"). [arXiv:2402.19427]

Full-period groups are scanned; leftover layers (26 mod 3 = 2) are unrolled.
Train/prefill runs the linear recurrence with ``jax.lax.associative_scan``
(parallel, TPU-friendly); decode is the exact one-step recurrence. The
recurrent state (B, W) plus a (conv_width-1) conv tail is the entire
"KV cache" of a rec layer — constant in sequence length, which is why this
family runs ``long_500k`` natively.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.sharding import constrain

_N_BLOCKS = 8   # block-diagonal gate projections (Griffin Appendix A)
_LRU_C = 8.0


def _pattern(cfg):
    return cfg.attn_pattern or ("rec", "rec", "attn")


def _plan(cfg):
    pat = _pattern(cfg)
    G = cfg.n_layers // len(pat)
    rest = tuple(pat[: cfg.n_layers - G * len(pat)])
    return G, pat, rest


# ---------------------------------------------------------------------------
# RG-LRU recurrent block
# ---------------------------------------------------------------------------

def _init_rec(key, cfg, dtype):
    D = cfg.d_model
    W = cfg.lru_width or D
    ks = jax.random.split(key, 8)
    nb = _N_BLOCKS
    # Lambda init so that a = exp(-c*softplus(L)) ** sigmoid(r) spans ~(0.9, 0.999)
    u = jax.random.uniform(ks[0], (W,), jnp.float32, 0.9, 0.999)
    lam = jnp.log(jnp.expm1(-jnp.log(u ** _LRU_C) / _LRU_C))
    return {
        "ln": L.init_norm(ks[1], D, cfg.norm, dtype),
        "w_gate": L.dense_init(ks[2], (D, W), dtype),
        "w_in": L.dense_init(ks[3], (D, W), dtype),
        "conv_w": L.dense_init(ks[4], (cfg.conv_width, W), dtype, scale=0.1),
        "conv_b": jnp.zeros((W,), dtype),
        "w_r": L.dense_init(ks[5], (nb, W // nb, W // nb), dtype),
        "w_i": L.dense_init(ks[6], (nb, W // nb, W // nb), dtype),
        "b_r": jnp.zeros((W,), dtype),
        "b_i": jnp.zeros((W,), dtype),
        "lam": lam.astype(jnp.float32),
        "w_out": L.dense_init(ks[7], (W, D), dtype),
    }


def _block_diag(u, w):
    """u: (..., W) @ block-diagonal w: (nb, W/nb, W/nb) -> (..., W)."""
    nb, bs, _ = w.shape
    shape = u.shape
    ub = u.reshape(*shape[:-1], nb, bs)
    out = jnp.einsum("...nb,nbk->...nk", ub, w)
    return out.reshape(*shape)


def _lru_gates(p, u):
    """Return (log_a, x_scaled) both (..., W) fp32."""
    uf = u.astype(jnp.float32)
    r = jax.nn.sigmoid(_block_diag(uf, p["w_r"].astype(jnp.float32)) + p["b_r"].astype(jnp.float32))
    i = jax.nn.sigmoid(_block_diag(uf, p["w_i"].astype(jnp.float32)) + p["b_i"].astype(jnp.float32))
    log_a = -_LRU_C * jax.nn.softplus(p["lam"]) * r            # (...,W) < 0
    a_sq = jnp.exp(2.0 * log_a)
    x = jnp.sqrt(jnp.maximum(1.0 - a_sq, 1e-12)) * (i * uf)
    return log_a, x


def _causal_conv(u, w, b, tail=None):
    """Depthwise causal conv. u: (B,S,W); w: (cw,W); tail: (B,cw-1,W)."""
    cw = w.shape[0]
    if tail is None:
        tail = jnp.zeros((u.shape[0], cw - 1, u.shape[2]), u.dtype)
    full = jnp.concatenate([tail, u], axis=1)
    out = sum(full[:, j:j + u.shape[1]] * w[j] for j in range(cw))
    new_tail = full[:, -(cw - 1):] if cw > 1 else tail
    return out + b, new_tail


def _rec_block(p, x, cfg, state, mode):
    B, S, D = x.shape
    h_in = L.apply_norm(x, p["ln"], cfg.norm, cfg.norm_eps)
    gate = jax.nn.gelu(h_in @ p["w_gate"])
    u = h_in @ p["w_in"]
    gate = constrain(gate, "batch", None, "lru")
    u = constrain(u, "batch", None, "lru")
    conv_tail, h_lru = state
    u, conv_tail = _causal_conv(u, p["conv_w"], p["conv_b"], conv_tail)
    log_a, xs = _lru_gates(p, u)
    if mode == "decode":
        h_new = jnp.exp(log_a[:, 0]) * h_lru + xs[:, 0]        # (B,W)
        y = h_new[:, None]
        state = (conv_tail, h_new)
    else:
        # h_t = a_t h_{t-1} + x_t ; associative scan over S, fp32
        def combine(c1, c2):
            a1, b1 = c1
            a2, b2 = c2
            return (a2 + a1, b2 + jnp.exp(a2) * b1)
        la, xb = jax.lax.associative_scan(combine, (log_a, xs), axis=1)
        y = xb + jnp.exp(la) * h_lru[:, None]
        state = (conv_tail, y[:, -1])
    out = (y.astype(x.dtype) * gate) @ p["w_out"]
    return x + out, state


def _rec_state(cfg, batch, dtype):
    W = cfg.lru_width or cfg.d_model
    return (jnp.zeros((batch, cfg.conv_width - 1, W), dtype),
            jnp.zeros((batch, W), jnp.float32))


# ---------------------------------------------------------------------------
# attention + mlp slots (reuse shared layers)
# ---------------------------------------------------------------------------

def _init_attn_slot(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    return {
        "ln1": L.init_norm(ks[0], cfg.d_model, cfg.norm, dtype),
        "ln2": L.init_norm(ks[1], cfg.d_model, cfg.norm, dtype),
        "attn": L.init_attention(ks[2], cfg, dtype),
        "ffn": L.init_mlp(ks[3], cfg, dtype),
    }


def _init_rec_slot(key, cfg, dtype):
    ks = jax.random.split(key, 3)
    return {
        "rec": _init_rec(ks[0], cfg, dtype),
        "ln2": L.init_norm(ks[1], cfg.d_model, cfg.norm, dtype),
        "ffn": L.init_mlp(ks[2], cfg, dtype),
    }


def _attn_apply(p, x, cfg, cache, mode, pos):
    h = L.apply_norm(x, p["ln1"], cfg.norm, cfg.norm_eps)
    w = cfg.window
    if mode == "train":
        a, new_cache = L.attn_forward(p["attn"], h, cfg, window=w), cache
    elif mode == "prefill":
        a, kc, vc = L.attn_prefill(p["attn"], h, cfg, cache["k"], cache["v"],
                                   window=w)
        new_cache = {"k": kc, "v": vc}
    else:
        a, kc, vc = L.attn_decode(p["attn"], h, cfg, cache["k"], cache["v"],
                                  pos, window=w)
        new_cache = {"k": kc, "v": vc}
    x = x + a
    h = L.apply_norm(x, p["ln2"], cfg.norm, cfg.norm_eps)
    x = x + L.mlp_forward(p["ffn"], h, cfg)
    return constrain(x, "batch", None, "d_model"), new_cache


def _rec_apply(p, x, cfg, state, mode, pos):
    x, new_state = _rec_block(p["rec"], x, cfg, state, mode)
    h = L.apply_norm(x, p["ln2"], cfg.norm, cfg.norm_eps)
    x = x + L.mlp_forward(p["ffn"], h, cfg)
    return constrain(x, "batch", None, "d_model"), new_state


def _slot_cache(cfg, kind, batch, max_len, dtype, window):
    if kind == "rec":
        return _rec_state(cfg, batch, dtype)
    Sc = min(max_len, window or cfg.window or max_len)
    def z():
        return jnp.zeros(L.kv_cache_shape(batch, Sc, cfg.n_kv_heads,
                                          cfg.head_dim), dtype)
    return {"k": z(), "v": z()}


# ---------------------------------------------------------------------------
# stack
# ---------------------------------------------------------------------------

def init_params(cfg, key, dtype=jnp.bfloat16):
    G, pat, rest = _plan(cfg)
    ks = jax.random.split(key, 3 + len(pat) + len(rest))
    init1 = {"rec": _init_rec_slot, "attn": _init_attn_slot}
    slots = []
    for i, kind in enumerate(pat):
        layer_keys = jax.random.split(ks[3 + i], G)
        slots.append(jax.vmap(lambda k: init1[kind](k, cfg, dtype))(layer_keys))
    rest_params = tuple(init1[kind](ks[3 + len(pat) + j], cfg, dtype)
                        for j, kind in enumerate(rest))
    return {
        "embed": L.embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype),
        "unembed": L.dense_init(ks[1], (cfg.d_model, cfg.vocab_size), dtype),
        "final_norm": L.init_norm(ks[2], cfg.d_model, cfg.norm, dtype),
        "slots": tuple(slots),
        "rest": rest_params,
    }


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
               window: Optional[int] = None):
    G, pat, rest = _plan(cfg)
    def stack(c):
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (G, *a.shape)), c)
    return {
        "slots": tuple(stack(_slot_cache(cfg, k, batch, max_len, dtype, window))
                       for k in pat),
        "rest": tuple(_slot_cache(cfg, k, batch, max_len, dtype, window)
                      for k in rest),
        "pos": jnp.zeros((), jnp.int32),
    }


def _run_stack(params, x, cfg, mode, cache, remat=False):
    G, pat, rest = _plan(cfg)
    apply1 = {"rec": _rec_apply, "attn": _attn_apply}
    pos = cache["pos"] if cache is not None else 0

    def body(x, xs):
        slot_params, caches = xs
        new = []
        for i, kind in enumerate(pat):
            x, st = apply1[kind](slot_params[i], x, cfg,
                                 caches[i] if caches is not None else None,
                                 mode, pos)
            new.append(st)
        return x, tuple(new)

    if remat and mode == "train":
        body = jax.checkpoint(body, prevent_cse=False)
    caches = cache["slots"] if cache is not None else tuple(
        _slot_cache(cfg, k, x.shape[0], 0, x.dtype, None) for k in pat)
    if mode == "train":
        caches = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (G, *a.shape)), tuple(
                _slot_cache(cfg, k, x.shape[0], 1, x.dtype, None) for k in pat))
    scan = jax.lax.scan if mode == "train" else L.scan_layers
    x, new_slots = scan(body, x, (params["slots"], caches))
    new_rest = []
    rest_caches = cache["rest"] if cache is not None else [None] * len(rest)
    for j, kind in enumerate(rest):
        rc = rest_caches[j] if mode != "train" else \
            _slot_cache(cfg, kind, x.shape[0], 1, x.dtype, None)
        x, st = apply1[kind](params["rest"][j], x, cfg, rc, mode, pos)
        new_rest.append(st)
    return x, new_slots, tuple(new_rest)


def _embed(params, tokens):
    return constrain(jnp.take(params["embed"], tokens, axis=0),
                     "batch", None, "d_model")


def _logits(params, x, cfg):
    x = L.apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    return constrain(x @ params["unembed"], "batch", None, "vocab")


def forward_train(params, cfg, batch, *, window=None, remat=True):
    x = _embed(params, batch["tokens"])
    x, _, _ = _run_stack(params, x, cfg, "train", None, remat=remat)
    return _logits(params, x, cfg), jnp.zeros((), jnp.float32)


def prefill(params, cfg, batch, cache, *, window=None):
    tokens = batch["tokens"]
    x = _embed(params, tokens)
    x, slots, rest = _run_stack(params, x, cfg, "prefill", cache)
    last = _logits(params, x[:, -1:, :], cfg)[:, 0]
    return last, {"slots": slots, "rest": rest,
                  "pos": jnp.asarray(tokens.shape[1], jnp.int32)}


def decode_step(params, cfg, token, cache, *, window=None):
    if token.ndim == 1:
        token = token[:, None]
    x = _embed(params, token)
    x, slots, rest = _run_stack(params, x, cfg, "decode", cache)
    return _logits(params, x, cfg)[:, 0], {"slots": slots, "rest": rest,
                                           "pos": cache["pos"] + 1}
