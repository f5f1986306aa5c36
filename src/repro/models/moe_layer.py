"""Expert-parallel, dropless top-k MoE FFN with sort-based grouped dispatch.

The layer holds experts ``[lo, hi)`` of ``n_experts`` (``cfg.expert_range``;
every expert unless ``cfg.experts_held`` says otherwise) and routes each
token over all ``n_experts`` at the published top-k. The (token, expert)
pairs routed to the experts held here are sorted by expert, and each held
expert computes its rows with grouped SwiGLU matmuls
(``layers.expert_ffn``). No token is dropped in any mode. The result is
this share's part of the layer: what the experts held elsewhere would add
is left out, for the chips that hold them. Under sharding rules that
shard the experts over a mesh axis, each shard of that axis does the same
for its own experts and one sum over the axis joins the parts (expert
parallel, no token moves).

Routers: ``softmax`` (probabilities; the top-k are renormalized over
themselves) and ``sigmoid`` (independent scores; a learned bias added to
the scores picks the top-k but never weighs them; the chosen scores are
renormalized, ``s / (sum s + 1e-6)``).

Returns an aux load-balancing loss (Switch eq. 4) accumulated by the caller.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models import sharding as SH
from repro.models.layers import dense_init, expert_ffn, init_mlp, mlp_forward


def init_moe(key, cfg, dtype):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    lo, hi = cfg.expert_range
    ks = jax.random.split(key, 5)
    assert cfg.mlp == "swiglu", "experts are SwiGLU"
    p = {
        "router": dense_init(ks[0], (D, E), dtype, scale=0.02),
        "wi": dense_init(ks[1], (hi - lo, D, F), dtype),
        "wo": dense_init(ks[2], (hi - lo, F, D), dtype,
                         scale=1.0 / math.sqrt(F)),
        "wg": dense_init(ks[3], (hi - lo, D, F), dtype),
    }
    if cfg.router == "sigmoid":
        p["bias"] = jnp.zeros((E,), dtype)
    if cfg.shared_expert:
        p["shared"] = init_mlp(ks[4], cfg, dtype)
    return p


def route(p, x, cfg):
    """x: (T, D) -> (weights (T, k), experts (T, k), scores (T, E)): each
    token's chosen experts of all ``n_experts`` and their weights. The
    router's matmul runs at full float32 precision: the choice of the top-k
    is discrete, and bf16 operands would flip near-ties of it."""
    logits = jnp.matmul(x, p["router"],
                        precision=jax.lax.Precision.HIGHEST).astype(
                            jnp.float32)
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + p["bias"].astype(jnp.float32),
                                  cfg.top_k)
        g = jnp.take_along_axis(scores, chosen, axis=-1)
        return g / (jnp.sum(g, -1, keepdims=True) + 1e-6), chosen, scores
    scores = jax.nn.softmax(logits, axis=-1)
    g, chosen = jax.lax.top_k(scores, cfg.top_k)
    return g / jnp.sum(g, -1, keepdims=True), chosen, scores


def _held_ffn(xf, g, chosen, wg, wi, wo, lo):
    """The part of the experts ``[lo, lo + E_held)`` (``wg``'s leading
    axis) for tokens ``xf (T, D)`` routed to ``chosen (T, k)`` with weights
    ``g``: their pairs sorted by expert through ``expert_ffn``, 0 for the
    pairs of experts held elsewhere. ``lo`` may be traced."""
    T, k = chosen.shape
    n = wg.shape[0]
    local = chosen.reshape(-1) - lo
    held = (local >= 0) & (local < n)
    key = jnp.where(held, local, n)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=n + 1)[:n]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(sizes).astype(jnp.int32)])
    xs = jnp.take(xf, order // k, axis=0)
    ys = expert_ffn(xs, wg, wi, wo, offsets)

    # back to pair order; rows of experts held elsewhere are undefined, so
    # they are masked before the gate multiplies them (in the backward
    # too, where an undefined row times a zero cotangent may be NaN)
    pair = jnp.take(ys, jnp.argsort(order), axis=0).reshape(T, k, -1)
    pair = jnp.where(held.reshape(T, k, 1), pair, 0.0) * g[..., None]
    return jnp.sum(pair, axis=1)


def _expert_axis(cfg, batch_size: int):
    """The mesh axis the experts are sharded over, and the token axes, when
    the sharding rules shard both evenly; else None."""
    ax, mesh, batch = SH.rule("experts"), SH.mesh(), SH.rule("batch")
    if ax is None or mesh is None or isinstance(ax, tuple):
        return None
    lo, hi = cfg.expert_range
    batch = batch if isinstance(batch, tuple) else (batch,) if batch else ()
    n_tok = math.prod(mesh.shape[a] for a in batch)
    if (hi - lo) % mesh.shape[ax] or batch_size % n_tok:
        return None
    return ax, (batch or None)


def moe_forward(p, x, cfg):
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar)."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    lo = cfg.expert_range[0]
    xf = x.reshape(B * S, D)
    g, chosen, scores = route(p, xf, cfg)
    axes = _expert_axis(cfg, B)
    if axes is None:
        out = _held_ffn(xf, g, chosen, p["wg"], p["wi"], p["wo"], lo)
    else:
        # expert parallel under sharding rules: tokens stay where they are
        # (replicated over the expert axis), each shard computes the pairs
        # of its own experts, and one sum over the axis joins the parts
        ax, tok = axes

        def shard(xf, g, chosen, wg, wi, wo):
            # the tokens, the same on every shard, meet this shard's experts:
            # said so, their gradients are summed over the axis
            xf, g = (jax.lax.pcast(a, ax, to="varying") for a in (xf, g))
            first = lo + jax.lax.axis_index(ax) * wg.shape[0]
            return jax.lax.psum(_held_ffn(xf, g, chosen, wg, wi, wo, first),
                                ax)
        w = P(ax, None, None)
        out = shard_map(shard, mesh=SH.mesh(),
                        in_specs=(P(tok, None),) * 3 + (w,) * 3,
                        out_specs=P(tok, None))(
            xf, g, chosen, p["wg"], p["wi"], p["wo"])
    out = out.astype(x.dtype).reshape(B, S, D)

    # aux load-balance loss (Switch eq. 4), over all experts
    frac_tokens = jnp.mean(jnp.sum(jax.nn.one_hot(chosen, E), axis=1), axis=0)
    frac_scores = jnp.mean(scores, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_scores) / k
    if "shared" in p:
        out = out + mlp_forward(p["shared"], x, cfg)
    return out, aux
