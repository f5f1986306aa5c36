"""Decoder-only transformer family: dense, MoE (optionally interleaved), VLM,
and hybrids of attention and gated short convolutions (LFM2).

Each layer is one residual block: a pre-norm token mixer (GQA attention,
or a gated short convolution) and a pre-norm FFN (dense or MoE). Layers
form stacks: an optional leading stack (``cfg.n_dense_layers`` layers with
a dense FFN), then the rest. A stack repeats a period of slots ``G``
times and is iterated with ``jax.lax.scan`` over the groups, so compile
time/HLO size is O(1) in depth (126-layer Llama-3 405B compiles as fast as
a 2-layer smoke model). The cache holds, per slot, k/v for an attention
slot and the last ``conv_width - 1`` values of z for a convolution slot.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.moe_layer import init_moe, moe_forward
from repro.models.sharding import constrain, maybe_gather_params


def _periodic(kinds):
    """(one period of ``kinds``, number of periods): the shortest period."""
    n = len(kinds)
    for p in range(1, n + 1):
        if n % p == 0 and kinds == kinds[:p] * (n // p):
            return kinds[:p], n // p
    return kinds, 1


def _stacks(cfg):
    """The decoder's stacks in order: ``(name, slot kinds, groups)``, each
    slot kind a ``(mixer, ffn)`` pair; ``slots`` is the main stack, and
    ``lead`` the leading dense layers where the config has them."""
    kinds = tuple(zip(cfg.layer_kinds(), cfg.ffn_kinds()))
    lead = cfg.n_dense_layers
    out = [("lead", *_periodic(kinds[:lead]))] if lead else []
    return out + [("slots", *_periodic(kinds[lead:]))]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_slot(key, cfg, kind, dtype):
    mixer, ffn_kind = kind
    ks = jax.random.split(key, 4)
    p = {
        "ln1": L.init_norm(ks[0], cfg.d_model, cfg.norm, dtype),
        "ln2": L.init_norm(ks[1], cfg.d_model, cfg.norm, dtype),
    }
    if mixer == "conv":
        p["conv"] = L.init_conv(ks[2], cfg, dtype)
    else:
        p["attn"] = L.init_attention(ks[2], cfg, dtype)
    if ffn_kind == "moe":
        p["ffn"] = init_moe(ks[3], cfg, dtype)
    else:
        p["ffn"] = L.init_mlp(ks[3], cfg, dtype, d_ff=cfg.d_ff_dense or cfg.d_ff)
    return p


def init_params(cfg, key, dtype=jnp.bfloat16):
    stacks = _stacks(cfg)
    ks = jax.random.split(key, 3 + len(stacks[-1][1]))
    out = {
        "embed": L.embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype),
        "final_norm": L.init_norm(ks[2], cfg.d_model, cfg.norm, dtype),
    }
    if not cfg.tie_embeddings:
        out["unembed"] = L.dense_init(ks[1], (cfg.d_model, cfg.vocab_size),
                                      dtype)
    for name, kinds, G in stacks:
        slot_keys = ks[3:] if name == "slots" else jax.random.split(
            jax.random.fold_in(key, 1), len(kinds))
        out[name] = tuple(
            jax.vmap(lambda k, kind=kind: _init_slot(k, cfg, kind, dtype))(
                jax.random.split(slot_keys[i], G))
            for i, kind in enumerate(kinds))
    return out


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
               window: Optional[int] = None):
    """Per stack, per slot of its period, a leaf stacked over the stack's
    ``G`` groups: an attention slot's ``k`` and ``v`` are ``(G, B, Sc, K,
    hd)`` where ``head_dim`` is a multiple of 128, else ``(G, B, Sc, K *
    hd)`` (``layers.kv_cache_shape``); a convolution slot's ``conv`` is
    ``(G, B, W - 1, D)``. ``Sc`` is ``max_len``, or the window if shorter."""
    Sc = min(max_len, window) if window else max_len

    def entry(mixer, G):
        if mixer == "conv":
            return {"conv": jnp.zeros((G, batch, cfg.conv_width - 1,
                                       cfg.d_model), dtype)}
        kv = (G, *L.kv_cache_shape(batch, Sc, cfg.n_kv_heads, cfg.head_dim))
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}

    cache = {name: tuple(entry(mixer, G) for mixer, _ in kinds)
             for name, kinds, G in _stacks(cfg)}
    cache["pos"] = jnp.zeros((), jnp.int32)
    return cache


# ---------------------------------------------------------------------------
# block body (one group of slots)
# ---------------------------------------------------------------------------

def _ffn_apply(slot_p, x, cfg, ffn_kind):
    if ffn_kind == "moe":
        return moe_forward(slot_p["ffn"], x, cfg)
    return L.mlp_forward(slot_p["ffn"], x, cfg), jnp.zeros((), jnp.float32)


def _mix(p, h, cfg, mixer, mode, cache, pos, window):
    """The slot's token mixer: (output, its new cache entry or None)."""
    if mixer == "conv":
        if mode == "decode":
            a, st = L.conv_decode(p["conv"], h, cache["conv"])
        else:
            a, st = L.conv_prefill(p["conv"], h, cfg)
        return a, (None if mode == "train" else
                   {"conv": st.astype(cache["conv"].dtype)})
    if mode == "train":
        return L.attn_forward(p["attn"], h, cfg, window=window), None
    if mode == "prefill":
        a, kc, vc = L.attn_prefill(p["attn"], h, cfg, cache["k"], cache["v"],
                                   window=window)
    else:
        a, kc, vc = L.attn_decode(p["attn"], h, cfg, cache["k"], cache["v"],
                                  pos, window=window)
    return a, {"k": kc, "v": vc}


def _group_body(cfg, mode: str, window, kinds):
    def body(carry, xs):
        if mode == "train":
            x, aux = carry
            slot_params, caches, pos = xs, [None] * len(kinds), None
        else:
            x, aux, pos = carry
            slot_params, caches = xs
        new_caches = []
        for i, (mixer, ffn_kind) in enumerate(kinds):
            p = maybe_gather_params(slot_params[i])
            h = L.apply_norm(x, p["ln1"], cfg.norm, cfg.norm_eps)
            a, c = _mix(p, h, cfg, mixer, mode, caches[i], pos, window)
            new_caches.append(c)
            x = x + a
            x = constrain(x, "batch", "seq", "d_model")
            h = L.apply_norm(x, p["ln2"], cfg.norm, cfg.norm_eps)
            f, aux_i = _ffn_apply(p, h, cfg, ffn_kind)
            x = x + f
            x = constrain(x, "batch", "seq", "d_model")
            aux = aux + aux_i
        if mode == "train":
            return (x, aux), None
        return (x, aux, pos), tuple(new_caches)

    return body


def _run_stack(params, x, cfg, mode, cache=None, window=None, remat=False):
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    for name, kinds, _ in _stacks(cfg):
        body = _group_body(cfg, mode, window, kinds)
        if mode == "train":
            if remat:
                body = jax.checkpoint(body, prevent_cse=False)
            (x, aux), _ = jax.lax.scan(body, (x, aux), params[name])
            continue
        if remat and mode == "prefill":
            body = jax.checkpoint(body, prevent_cse=False)
        (x, aux, _), new_cache[name] = L.scan_layers(
            body, (x, aux, cache["pos"]), (params[name], cache[name]))
    return x, aux, new_cache


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def _embed(params, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    return constrain(x, "batch", None, "d_model")


def _logits(params, x, cfg):
    x = L.apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = L.proj(x, params["embed"], transposed=True)
    else:
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
    x, _, new_cache = _run_stack(params, x, cfg, "prefill", cache=cache,
                                 window=window)
    last = _logits(params, x[:, -1:, :], cfg)[:, 0]
    return last, {**new_cache, "pos": jnp.asarray(tokens.shape[1], jnp.int32)}


def decode_step(params, cfg, token, cache, *, window=None):
    """One decode step. token: (B,) or (B,1). Returns (logits (B,V), cache)."""
    if token.ndim == 1:
        token = token[:, None]
    x = _embed(params, token)
    x, _, new_cache = _run_stack(params, x, cfg, "decode", cache=cache,
                                 window=window)
    logits = _logits(params, x, cfg)[:, 0]
    return logits, {**new_cache, "pos": cache["pos"] + 1}
