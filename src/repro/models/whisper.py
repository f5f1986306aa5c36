"""Whisper-style encoder-decoder audio backbone. [arXiv:2212.04356]

Per the assignment carve-out, the mel-spectrogram + conv feature extractor is
a STUB: ``batch["enc_feats"]`` supplies precomputed frame embeddings
(B, encoder_seq, d_model). Everything downstream — 32-layer bidirectional
encoder, 32-layer causal decoder with self- and cross-attention KV caches —
is implemented here. Positions are sinusoidal (Whisper's encoder is
sinusoidal; its decoder uses learned positions — we use sinusoidal there too
so the position table does not dominate memory at the assignment's 32k/500k
decode shapes; recorded in DESIGN.md).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.sharding import constrain


def _init_enc_layer(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    return {
        "ln1": L.init_norm(ks[0], cfg.d_model, cfg.norm, dtype),
        "ln2": L.init_norm(ks[1], cfg.d_model, cfg.norm, dtype),
        "attn": L.init_attention(ks[2], cfg, dtype),
        "ffn": L.init_mlp(ks[3], cfg, dtype),
    }


def _init_dec_layer(key, cfg, dtype):
    ks = jax.random.split(key, 6)
    return {
        "ln1": L.init_norm(ks[0], cfg.d_model, cfg.norm, dtype),
        "ln_c": L.init_norm(ks[1], cfg.d_model, cfg.norm, dtype),
        "ln2": L.init_norm(ks[2], cfg.d_model, cfg.norm, dtype),
        "attn": L.init_attention(ks[3], cfg, dtype),
        "cross": L.init_attention(ks[4], cfg, dtype, cross=True),
        "ffn": L.init_mlp(ks[5], cfg, dtype),
    }


def init_params(cfg, key, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 6)
    enc_keys = jax.random.split(ks[3], cfg.n_encoder_layers)
    dec_keys = jax.random.split(ks[4], cfg.n_layers)
    return {
        "embed": L.embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype),
        "unembed": L.dense_init(ks[1], (cfg.d_model, cfg.vocab_size), dtype),
        "final_norm": L.init_norm(ks[2], cfg.d_model, cfg.norm, dtype),
        "enc_norm": L.init_norm(ks[5], cfg.d_model, cfg.norm, dtype),
        "enc": jax.vmap(lambda k: _init_enc_layer(k, cfg, dtype))(enc_keys),
        "dec": jax.vmap(lambda k: _init_dec_layer(k, cfg, dtype))(dec_keys),
    }


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
               window: Optional[int] = None):
    Ld = cfg.n_layers
    Sc = min(max_len, window) if window else max_len
    def kv(s):
        return jnp.zeros((Ld, batch, s, cfg.n_kv_heads, cfg.head_dim), dtype)
    self_kv = (Ld, *L.kv_cache_shape(batch, Sc, cfg.n_kv_heads, cfg.head_dim))
    return {
        "k": jnp.zeros(self_kv, dtype), "v": jnp.zeros(self_kv, dtype),
        "ck": kv(cfg.encoder_seq), "cv": kv(cfg.encoder_seq),
        "pos": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------

def encode(params, cfg, enc_feats):
    x = enc_feats + L.sinusoidal_pos(jnp.arange(enc_feats.shape[1]),
                                     cfg.d_model, enc_feats.dtype)
    x = constrain(x, "batch", None, "d_model")

    def body(x, p):
        h = L.apply_norm(x, p["ln1"], cfg.norm, cfg.norm_eps)
        x = x + L.attn_forward(p["attn"], h, cfg, causal=False)
        h = L.apply_norm(x, p["ln2"], cfg.norm, cfg.norm_eps)
        x = x + L.mlp_forward(p["ffn"], h, cfg)
        return constrain(x, "batch", None, "d_model"), None

    x, _ = jax.lax.scan(body, x, params["enc"])
    return L.apply_norm(x, params["enc_norm"], cfg.norm, cfg.norm_eps)


def _dec_embed(params, cfg, tokens, pos0):
    x = jnp.take(params["embed"], tokens, axis=0)
    p0 = jnp.asarray(pos0)
    if p0.ndim == 0:
        positions = (p0 + jnp.arange(tokens.shape[1]))[None, :]
    else:                                  # per-slot positions (B,)
        positions = p0[:, None] + jnp.arange(tokens.shape[1])[None, :]
    x = x + L.sinusoidal_pos(positions, cfg.d_model, x.dtype)
    return constrain(x, "batch", None, "d_model")


def _dec_stack(params, cfg, x, mode, cache, enc_out=None, window=None,
               remat=False):
    """mode: train|prefill|decode. For prefill, enc_out is required (cross K/V
    are computed and stored); for decode they are read from the cache."""
    pos = cache["pos"] if cache is not None else 0

    def body(x, xs):
        if mode == "train":
            p = xs
        else:
            p, (kc, vc, ck, cv) = xs
        h = L.apply_norm(x, p["ln1"], cfg.norm, cfg.norm_eps)
        if mode == "train":
            a = L.attn_forward(p["attn"], h, cfg, window=window)
            new = None
        elif mode == "prefill":
            a, kc, vc = L.attn_prefill(p["attn"], h, cfg, kc, vc, window=window)
            ck, cv = L.cross_attn_cache(p["cross"], enc_out, cfg)
            new = (kc, vc, ck, cv)
        else:
            a, kc, vc = L.attn_decode(p["attn"], h, cfg, kc, vc, pos,
                                      window=window)
            new = (kc, vc, ck, cv)
        x = x + a
        h = L.apply_norm(x, p["ln_c"], cfg.norm, cfg.norm_eps)
        if mode == "train":
            x = x + L.cross_attn_apply(p["cross"], h, cfg,
                                       *L.cross_attn_cache(p["cross"], enc_out, cfg))
        else:
            x = x + L.cross_attn_apply(p["cross"], h, cfg, ck, cv)
        h = L.apply_norm(x, p["ln2"], cfg.norm, cfg.norm_eps)
        x = x + L.mlp_forward(p["ffn"], h, cfg)
        return constrain(x, "batch", None, "d_model"), new

    if mode == "train":
        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = jax.lax.scan(body, x, params["dec"])
        return x, None
    xs = (params["dec"], (cache["k"], cache["v"], cache["ck"], cache["cv"]))
    x, new = L.scan_layers(body, x, xs)
    return x, new


def _logits(params, x, cfg):
    x = L.apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    return constrain(x @ params["unembed"], "batch", None, "vocab")


def forward_train(params, cfg, batch, *, window=None, remat=True):
    enc_out = encode(params, cfg, batch["enc_feats"])
    x = _dec_embed(params, cfg, batch["tokens"], 0)
    x, _ = _dec_stack(params, cfg, x, "train", None, enc_out=enc_out,
                      window=window, remat=remat)
    return _logits(params, x, cfg), jnp.zeros((), jnp.float32)


def prefill(params, cfg, batch, cache, *, window=None):
    enc_out = encode(params, cfg, batch["enc_feats"])
    tokens = batch["tokens"]
    x = _dec_embed(params, cfg, tokens, 0)
    x, new = _dec_stack(params, cfg, x, "prefill", cache, enc_out=enc_out,
                        window=window)
    kc, vc, ck, cv = new
    last = _logits(params, x[:, -1:, :], cfg)[:, 0]
    return last, {"k": kc, "v": vc, "ck": ck, "cv": cv,
                  "pos": jnp.asarray(tokens.shape[1], jnp.int32)}


def decode_step(params, cfg, token, cache, *, window=None):
    if token.ndim == 1:
        token = token[:, None]
    x = _dec_embed(params, cfg, token, cache["pos"])
    x, new = _dec_stack(params, cfg, x, "decode", cache, window=window)
    kc, vc, ck, cv = new
    return _logits(params, x, cfg)[:, 0], {"k": kc, "v": vc, "ck": ck,
                                           "cv": cv, "pos": cache["pos"] + 1}
