"""Decoder-only transformer family: dense, MoE (optionally interleaved), VLM.

Layers are stacked into ``groups`` of ``moe_every`` slots and iterated with
``jax.lax.scan`` so compile time/HLO size is O(1) in depth (126-layer Llama-3
405B compiles as fast as a 2-layer smoke model). Each slot is one residual
block: pre-norm attention + pre-norm FFN (dense or MoE).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.moe_layer import init_moe, moe_forward
from repro.models.sharding import constrain, maybe_gather_params


def _slot_kinds(cfg):
    return cfg.ffn_kinds()[: cfg.moe_every]


def _n_groups(cfg):
    assert cfg.n_layers % max(cfg.moe_every, 1) == 0, (
        f"{cfg.name}: n_layers={cfg.n_layers} must divide moe_every={cfg.moe_every}")
    return cfg.n_layers // cfg.moe_every


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_slot(key, cfg, ffn_kind, dtype):
    ks = jax.random.split(key, 4)
    p = {
        "ln1": L.init_norm(ks[0], cfg.d_model, cfg.norm, dtype),
        "ln2": L.init_norm(ks[1], cfg.d_model, cfg.norm, dtype),
        "attn": L.init_attention(ks[2], cfg, dtype),
    }
    if ffn_kind == "moe":
        p["ffn"] = init_moe(ks[3], cfg, dtype)
    else:
        p["ffn"] = L.init_mlp(ks[3], cfg, dtype, d_ff=cfg.d_ff_dense or cfg.d_ff)
    return p


def init_params(cfg, key, dtype=jnp.bfloat16):
    G = _n_groups(cfg)
    kinds = _slot_kinds(cfg)
    ks = jax.random.split(key, 3 + len(kinds))
    slots = []
    for i, kind in enumerate(kinds):
        layer_keys = jax.random.split(ks[3 + i], G)
        slot = jax.vmap(lambda k: _init_slot(k, cfg, kind, dtype))(layer_keys)
        slots.append(slot)
    return {
        "embed": L.embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype),
        "unembed": L.dense_init(ks[1], (cfg.d_model, cfg.vocab_size), dtype),
        "final_norm": L.init_norm(ks[2], cfg.d_model, cfg.norm, dtype),
        "slots": tuple(slots),
    }


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
               window: Optional[int] = None):
    G = _n_groups(cfg)
    Sc = min(max_len, window) if window else max_len
    def kv():
        return jnp.zeros((G, batch, Sc, cfg.n_kv_heads, cfg.head_dim), dtype)
    return {
        "slots": tuple({"k": kv(), "v": kv()} for _ in _slot_kinds(cfg)),
        "pos": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# block body (one group of slots)
# ---------------------------------------------------------------------------

def _ffn_apply(slot_p, x, cfg, ffn_kind, mode):
    if ffn_kind == "moe":
        out, aux = moe_forward(slot_p["ffn"], x, cfg,
                               dropless=(mode == "decode"))
        return out, aux
    return L.mlp_forward(slot_p["ffn"], x, cfg), jnp.zeros((), jnp.float32)


def _group_body(cfg, mode: str, window):
    kinds = _slot_kinds(cfg)

    def body(carry, xs):
        if mode == "train":
            x, aux = carry
            slot_params = xs
            new_caches = None
        else:
            x, aux, pos = carry
            slot_params, caches = xs
            new_caches = []
        for i, ffn_kind in enumerate(kinds):
            p = maybe_gather_params(slot_params[i])
            h = L.apply_norm(x, p["ln1"], cfg.norm, cfg.norm_eps)
            if mode == "train":
                a = L.attn_forward(p["attn"], h, cfg, window=window)
            elif mode == "prefill":
                a, kc, vc = L.attn_prefill(p["attn"], h, cfg, caches[i]["k"],
                                           caches[i]["v"], window=window)
                new_caches.append({"k": kc, "v": vc})
            else:  # decode
                a, kc, vc = L.attn_decode(p["attn"], h, cfg, caches[i]["k"],
                                          caches[i]["v"], pos, window=window)
                new_caches.append({"k": kc, "v": vc})
            x = x + a
            x = constrain(x, "batch", "seq", "d_model")
            h = L.apply_norm(x, p["ln2"], cfg.norm, cfg.norm_eps)
            f, aux_i = _ffn_apply(p, h, cfg, ffn_kind, mode)
            x = x + f
            x = constrain(x, "batch", "seq", "d_model")
            aux = aux + aux_i
        if mode == "train":
            return (x, aux), None
        return (x, aux, pos), tuple(new_caches)

    return body


def _run_stack(params, x, cfg, mode, cache=None, window=None, remat=False):
    body = _group_body(cfg, mode, window)
    if mode == "train":
        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["slots"])
        return x, aux, None
    pos = cache["pos"]
    if remat and mode == "prefill":
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux, _), new_slots = L.scan_layers(
        body, (x, jnp.zeros((), jnp.float32), pos),
        (params["slots"], cache["slots"]))
    return x, aux, new_slots


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def _embed(params, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    return constrain(x, "batch", None, "d_model")


def _logits(params, x, cfg):
    x = L.apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    logits = L.proj(x, params["unembed"])
    return constrain(logits, "batch", None, "vocab")


def forward_train(params, cfg, batch, *, window=None, remat=True):
    """Full-sequence forward. Returns (logits (B,S,V), aux_loss)."""
    x = _embed(params, batch["tokens"])
    x, aux, _ = _run_stack(params, x, cfg, "train", window=window, remat=remat)
    return _logits(params, x, cfg), aux


def prefill(params, cfg, batch, cache, *, window=None):
    """Process the prompt, fill the cache. Returns (last-token logits, cache)."""
    tokens = batch["tokens"]
    x = _embed(params, tokens)
    x, _, new_slots = _run_stack(params, x, cfg, "prefill", cache=cache,
                                 window=window)
    last = _logits(params, x[:, -1:, :], cfg)[:, 0]
    return last, {"slots": new_slots, "pos": jnp.asarray(tokens.shape[1], jnp.int32)}


def decode_step(params, cfg, token, cache, *, window=None):
    """One decode step. token: (B,) or (B,1). Returns (logits (B,V), cache)."""
    if token.ndim == 1:
        token = token[:, None]
    x = _embed(params, token)
    x, _, new_slots = _run_stack(params, x, cfg, "decode", cache=cache,
                                 window=window)
    logits = _logits(params, x, cfg)[:, 0]
    return logits, {"slots": new_slots, "pos": cache["pos"] + 1}
