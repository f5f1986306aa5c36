"""xLSTM family: mLSTM (matrix-memory, chunkwise-parallel) + sLSTM (scalar-
memory, sequential) blocks. [arXiv:2405.04517]

Layout: every ``slstm_every``-th layer is sLSTM, the rest mLSTM (7:1 in the
assigned 350M config). Layers are stacked into groups of ``slstm_every`` and
scanned, like the transformer family.

The mLSTM uses the stabilized chunkwise formulation (intra-chunk quadratic +
inter-chunk recurrent carry) for train/prefill, and the exact single-step
recurrence for decode, so decode is O(d^2) per token with *no* KV growth —
this is what makes the xLSTM "KV cache" a fixed-size state that RAPID's
disaggregated handoff transfers in one small message.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.sharding import constrain

CHUNK = 256
_NEG = -1e30


def _period(cfg):
    return cfg.slstm_every if cfg.slstm_every else cfg.n_layers


def _slot_kinds(cfg):
    return cfg.layer_kinds()[: _period(cfg)]


def _n_groups(cfg):
    p = _period(cfg)
    assert cfg.n_layers % p == 0
    return cfg.n_layers // p


# ---------------------------------------------------------------------------
# mLSTM block
# ---------------------------------------------------------------------------

def _init_mlstm(key, cfg, dtype):
    D = cfg.d_model
    inner = 2 * D
    ks = jax.random.split(key, 8)
    return {
        "ln": L.init_norm(ks[0], D, cfg.norm, dtype),
        "w_up": L.dense_init(ks[1], (D, inner), dtype),
        "w_gate": L.dense_init(ks[2], (D, inner), dtype),
        "wq": L.dense_init(ks[3], (inner, inner), dtype),
        "wk": L.dense_init(ks[4], (inner, inner), dtype),
        "wv": L.dense_init(ks[5], (inner, inner), dtype),
        "w_if": L.dense_init(ks[6], (inner, 2 * cfg.n_heads), dtype, scale=0.02),
        "b_if": jnp.concatenate([jnp.zeros((cfg.n_heads,), dtype),
                                 jnp.full((cfg.n_heads,), 3.0, dtype)]),
        "w_down": L.dense_init(ks[7], (inner, D), dtype,
                               scale=1.0 / math.sqrt(inner)),
    }


def _mlstm_qkvif(p, x, cfg):
    """x: (B, S, D) -> q,k,v (B,S,nh,hd) fp32; log_i, log_f (B,S,nh) fp32."""
    B, S, _ = x.shape
    nh = cfg.n_heads
    up = x @ p["w_up"]
    inner = up.shape[-1]
    hd = inner // nh
    q = (up @ p["wq"]).reshape(B, S, nh, hd).astype(jnp.float32)
    k = (up @ p["wk"]).reshape(B, S, nh, hd).astype(jnp.float32) / math.sqrt(hd)
    v = (up @ p["wv"]).reshape(B, S, nh, hd).astype(jnp.float32)
    gif = (up @ p["w_if"] + p["b_if"]).astype(jnp.float32)
    log_i, f_raw = gif[..., :nh], gif[..., nh:]
    log_f = jax.nn.log_sigmoid(f_raw)
    gate = jax.nn.silu(x @ p["w_gate"])
    return q, k, v, log_i, log_f, gate


def _mlstm_chunk_scan(q, k, v, log_i, log_f, state):
    """Chunkwise-parallel stabilized mLSTM. Shapes: q,k,v (B,S,nh,hd);
    gates (B,S,nh). state = (C (B,nh,hd,hd), n (B,nh,hd), m (B,nh)).
    Returns (h (B,S,nh,hd), new_state)."""
    B, S, nh, hd = q.shape
    nc = -(-S // CHUNK)
    pad = nc * CHUNK - S
    if pad:
        def padfn(a, fill=0.0):
            return jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2),
                           constant_values=fill)
        q, k, v = padfn(q), padfn(k), padfn(v)
        log_i = padfn(log_i, _NEG)   # padded steps inject nothing
        log_f = padfn(log_f, 0.0)    # ... and do not decay the state
    def ch(a):
        return a.reshape(B, nc, CHUNK, *a.shape[2:]).swapaxes(0, 1)
    qc, kc, vc, lic, lfc = map(ch, (q, k, v, log_i, log_f))  # (nc,B,C,...)

    def chunk_body(carry, xs):
        C, n, m = carry                       # (B,nh,hd,hd),(B,nh,hd),(B,nh)
        qq, kk, vv, li, lf = xs               # (B,C,nh,hd) / (B,C,nh)
        F = jnp.cumsum(lf, axis=1)            # (B,C,nh) inclusive cumsum
        # D[t,s] = F_t - F_s + li_s for s <= t
        Dm = (F[:, :, None, :] - F[:, None, :, :] + li[:, None, :, :])
        tri = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
        Dm = jnp.where(tri[None, :, :, None], Dm, _NEG)   # (B,t,s,nh)
        m_intra = jnp.max(Dm, axis=2)                     # (B,C,nh)
        m_inter = m[:, None, :] + F                       # (B,C,nh)
        m_row = jnp.maximum(m_intra, m_inter)             # (B,C,nh)
        scores = jnp.einsum("bthd,bshd->btsh", qq, kk)    # (B,t,s,nh)
        w = scores * jnp.exp(Dm - m_row[:, :, None, :])
        intra = jnp.einsum("btsh,bshd->bthd", w, vv)
        inter = jnp.exp(m_inter - m_row)[..., None] * \
            jnp.einsum("bthd,bhde->bthe", qq, C)
        h_num = intra + inter
        qn = jnp.einsum("bthd,bhd->bth", qq, n)
        denom = jnp.abs(jnp.einsum("btsh->bth", w) +
                        jnp.exp(m_inter - m_row) * qn)
        denom = jnp.maximum(denom, jnp.exp(-m_row))
        h = h_num / denom[..., None]
        # chunk-end state update
        FL = F[:, -1:, :]                                  # (B,1,nh)
        log_w = FL - F + li                                # (B,C,nh)
        m_next = jnp.maximum(m + FL[:, 0], jnp.max(log_w, axis=1))
        scale_old = jnp.exp(m + FL[:, 0] - m_next)         # (B,nh)
        w_s = jnp.exp(log_w - m_next[:, None, :])          # (B,C,nh)
        C_new = C * scale_old[..., None, None] + \
            jnp.einsum("bsh,bshd,bshe->bhde", w_s, kk, vv)
        n_new = n * scale_old[..., None] + jnp.einsum("bsh,bshd->bhd", w_s, kk)
        return (C_new, n_new, m_next), h

    (C, n, m), hs = jax.lax.scan(chunk_body, state, (qc, kc, vc, lic, lfc))
    h = hs.swapaxes(0, 1).reshape(B, nc * CHUNK, nh, hd)
    if pad:
        h = h[:, :S]
    return h, (C, n, m)


def _mlstm_decode(q, k, v, log_i, log_f, state):
    """Single-step mLSTM. q,k,v: (B,nh,hd); gates (B,nh)."""
    C, n, m = state
    m_new = jnp.maximum(log_f + m, log_i)
    f_s = jnp.exp(log_f + m - m_new)
    i_s = jnp.exp(log_i - m_new)
    C = C * f_s[..., None, None] + i_s[..., None, None] * \
        jnp.einsum("bhd,bhe->bhde", k, v)
    n = n * f_s[..., None] + i_s[..., None] * k
    num = jnp.einsum("bhd,bhde->bhe", q, C)
    denom = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q, n)),
                        jnp.exp(-m_new))
    h = num / denom[..., None]
    return h, (C, n, m_new)


def _mlstm_block(p, x, cfg, state, mode):
    B, S, D = x.shape
    h_in = L.apply_norm(x, p["ln"], cfg.norm, cfg.norm_eps)
    q, k, v, log_i, log_f, gate = _mlstm_qkvif(p, h_in, cfg)
    nh = cfg.n_heads
    hd = q.shape[-1]
    if mode == "decode":
        hq, new_state = _mlstm_decode(q[:, 0], k[:, 0], v[:, 0],
                                      log_i[:, 0], log_f[:, 0], state)
        h = hq[:, None]
    else:
        h, new_state = _mlstm_chunk_scan(q, k, v, log_i, log_f, state)
    h = h.reshape(B, S, nh * hd).astype(x.dtype)
    out = (h * gate) @ p["w_down"]
    return x + out, new_state


def _mlstm_state(cfg, batch, dtype):
    nh = cfg.n_heads
    hd = (2 * cfg.d_model) // nh
    return (jnp.zeros((batch, nh, hd, hd), jnp.float32),
            jnp.zeros((batch, nh, hd), jnp.float32),
            jnp.full((batch, nh), _NEG, jnp.float32))


# ---------------------------------------------------------------------------
# sLSTM block
# ---------------------------------------------------------------------------

def _init_slstm(key, cfg, dtype):
    D = cfg.d_model
    nh = cfg.n_heads
    hd = D // nh
    ks = jax.random.split(key, 4)
    return {
        "ln": L.init_norm(ks[0], D, cfg.norm, dtype),
        "w_in": L.dense_init(ks[1], (D, 4 * D), dtype),
        "b_in": jnp.zeros((4 * D,), dtype),
        "r": L.dense_init(ks[2], (nh, hd, 4 * hd), dtype),
        "w_out": L.dense_init(ks[3], (D, D), dtype),
    }


def _slstm_step(p, cfg, pre_x, state):
    """One sLSTM step. pre_x: (B, 4D) input preactivation (x @ w_in + b)."""
    c, n, m, h = state                    # each (B, D)
    B, D4 = pre_x.shape
    D = D4 // 4
    nh = cfg.n_heads
    hd = D // nh
    hr = h.reshape(B, nh, hd)
    rec = jnp.einsum("bnh,nhk->bnk", hr, p["r"])        # (B, nh, 4*hd)
    # per-head (z,i,f,o) blocks -> global (z,i,f,o) layout matching w_in
    rec = rec.reshape(B, nh, 4, hd).swapaxes(1, 2).reshape(B, 4 * D)
    pre = pre_x + rec
    z, i_raw, f_raw, o_raw = jnp.split(pre.astype(jnp.float32), 4, axis=-1)
    log_f = jax.nn.log_sigmoid(f_raw)
    m_new = jnp.maximum(log_f + m, i_raw)
    i_s = jnp.exp(i_raw - m_new)
    f_s = jnp.exp(log_f + m - m_new)
    c_new = f_s * c + i_s * jnp.tanh(z)
    n_new = f_s * n + i_s
    h_new = jax.nn.sigmoid(o_raw) * c_new / jnp.maximum(n_new, 1.0)
    return (c_new, n_new, m_new, h_new)


def _slstm_block(p, x, cfg, state, mode):
    B, S, D = x.shape
    h_in = L.apply_norm(x, p["ln"], cfg.norm, cfg.norm_eps)
    pre = (h_in @ p["w_in"] + p["b_in"])
    if mode == "decode":
        state = _slstm_step(p, cfg, pre[:, 0], state)
        hs = state[3][:, None]
    else:
        def step(st, px):
            st = _slstm_step(p, cfg, px, st)
            return st, st[3]
        state, hs = jax.lax.scan(step, state, pre.swapaxes(0, 1))
        hs = hs.swapaxes(0, 1)             # (B, S, D)
    out = hs.astype(x.dtype) @ p["w_out"]
    return x + out, state


def _slstm_state(cfg, batch, dtype):
    D = cfg.d_model
    def z():
        return jnp.zeros((batch, D), jnp.float32)
    return (z(), z(), jnp.full((batch, D), _NEG, jnp.float32), z())


# ---------------------------------------------------------------------------
# stack plumbing (same group-scan pattern as transformer.py)
# ---------------------------------------------------------------------------

def init_params(cfg, key, dtype=jnp.bfloat16):
    G = _n_groups(cfg)
    kinds = _slot_kinds(cfg)
    ks = jax.random.split(key, 3 + len(kinds))
    slots = []
    for i, kind in enumerate(kinds):
        init1 = _init_mlstm if kind == "mlstm" else _init_slstm
        layer_keys = jax.random.split(ks[3 + i], G)
        slots.append(jax.vmap(lambda k: init1(k, cfg, dtype))(layer_keys))
    return {
        "embed": L.embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype),
        "unembed": L.dense_init(ks[1], (cfg.d_model, cfg.vocab_size), dtype),
        "final_norm": L.init_norm(ks[2], cfg.d_model, cfg.norm, dtype),
        "slots": tuple(slots),
    }


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    G = _n_groups(cfg)
    kinds = _slot_kinds(cfg)
    def stack(mk):
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (G, *a.shape)), mk)
    slots = tuple(stack(_mlstm_state(cfg, batch, dtype) if k == "mlstm"
                        else _slstm_state(cfg, batch, dtype)) for k in kinds)
    return {"slots": slots, "pos": jnp.zeros((), jnp.int32)}


def _run_stack(params, x, cfg, mode, cache):
    kinds = _slot_kinds(cfg)

    def body(carry, xs):
        x = carry
        slot_params, states = xs
        new_states = []
        for i, kind in enumerate(kinds):
            blk = _mlstm_block if kind == "mlstm" else _slstm_block
            x, st = blk(slot_params[i], x, cfg, states[i], mode)
            x = constrain(x, "batch", None, "d_model")
            new_states.append(st)
        return x, tuple(new_states)

    x, new_slots = jax.lax.scan(body, x, (params["slots"], cache["slots"]))
    return x, new_slots


def _embed(params, tokens):
    return constrain(jnp.take(params["embed"], tokens, axis=0),
                     "batch", None, "d_model")


def _logits(params, x, cfg):
    x = L.apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    return constrain(x @ params["unembed"], "batch", None, "vocab")


def forward_train(params, cfg, batch):
    x = _embed(params, batch["tokens"])
    cache = init_cache(cfg, x.shape[0], 0, x.dtype)
    x, _ = _run_stack(params, x, cfg, "train", cache)
    return _logits(params, x, cfg), jnp.zeros((), jnp.float32)


def prefill(params, cfg, batch, cache):
    tokens = batch["tokens"]
    x = _embed(params, tokens)
    x, new_slots = _run_stack(params, x, cfg, "prefill", cache)
    last = _logits(params, x[:, -1:, :], cfg)[:, 0]
    return last, {"slots": new_slots,
                  "pos": jnp.asarray(tokens.shape[1], jnp.int32)}


def decode_step(params, cfg, token, cache):
    if token.ndim == 1:
        token = token[:, None]
    x = _embed(params, token)
    x, new_slots = _run_stack(params, x, cfg, "decode", cache)
    return _logits(params, x, cfg)[:, 0], {"slots": new_slots,
                                           "pos": cache["pos"] + 1}
