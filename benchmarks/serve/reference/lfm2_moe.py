"""Plain reference for an LFM2 MoE decoder (gated short convolutions beside
GQA attention, leading dense layers, a sigmoid-routed expert FFN in the
rest), written from the configuration file's statement of the equations
(``equations`` in ``configs/*.json``).

It imports nothing of the program under test. Weights are the benchmark's
own, made here from a seed; the harness hands the same arrays to the
program in the program's layout.

Layers form two parts: ``lead`` (the first ``num_dense_layers``, dense
FFN) and ``main`` (the rest, expert FFN). Each part repeats the shortest
period of its ``layer_types`` ``G`` times, and every weight of the slot
``j`` of a period is stacked over the ``G`` periods under the name
``<part><j>_<weight>``: ``op_norm``, ``ffn_norm (G, D)``; a conv slot
``conv_in (G, D, 3D)``, ``conv_w (G, L, D)`` (tap ``i`` multiplies
``z_{t-L+1+i}``), ``conv_out (G, D, D)``; an attention slot ``wq (G, D,
H*hd)``, ``wk``/``wv (G, D, K*hd)``, ``wo (G, H*hd, D)``, ``q_norm``/
``k_norm (G, hd)``; a dense FFN ``w1``/``w3 (G, D, F)``, ``w2 (G, F, D)``;
an expert FFN ``router (G, D, E)``, ``router_bias (G, E)`` over all ``E``
routed experts, and ``ew1``/``ew3 (G, Eh, D, Fe)``, ``ew2 (G, Eh, Fe, D)``
for the ``Eh`` experts held here. Besides: ``embed (V, D)``, the
unembedding too (tied), and ``norm_f_w (D)``.

``forward`` runs the whole sequence without a cache in float32: at
``HIGHEST`` matmul precision it is the reference; ``control_gaps`` runs
it at ``DEFAULT`` with int8 weights. Only the held experts contribute,
as on the chip that holds them.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

BIAS_STD = 0.05     # selection bias: reorders the top-4 for some tokens


class Shape(NamedTuple):
    layer_types: Tuple[str, ...]     # "conv" | "full_attention" per layer
    n_dense: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int                          # dense FFN width
    expert_ff: int
    vocab: int
    experts: int                     # routed over
    held: Tuple[int, int]            # [lo, hi) of them held here
    top_k: int
    conv_len: int
    eps: float
    rope_theta: float
    routed_scale: float


def shape_of(c: dict) -> Shape:
    """The reference's view of a configuration file."""
    if c["model_type"] != "lfm2_moe" or c["conv_bias"]:
        raise ValueError("no reference for this configuration")
    if not (c["norm_topk_prob"] and c["use_expert_bias"]):
        raise ValueError("the equations renormalize the top-k and add a "
                         "selection bias")
    lo, hi = c["experts_held"]
    if hi - lo != c["num_experts"]:
        raise ValueError("num_experts counts the experts held here")
    return Shape(
        layer_types=tuple(c["layer_types"]), n_dense=c["num_dense_layers"],
        d=c["hidden_size"], heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        ff=c["intermediate_size"], expert_ff=c["moe_intermediate_size"],
        vocab=c["vocab_size"],
        experts=c["source_values"].get("num_experts", c["num_experts"]),
        held=(lo, hi), top_k=c["num_experts_per_tok"],
        conv_len=c["conv_L_cache"], eps=float(c["norm_eps"]),
        rope_theta=float(c["rope_parameters"]["rope_theta"]),
        routed_scale=float(c["routed_scaling_factor"]))


def _period(kinds: tuple):
    n = len(kinds)
    for p in range(1, n + 1):
        if n % p == 0 and kinds == kinds[:p] * (n // p):
            return kinds[:p], n // p
    return kinds, 1


def parts(s: Shape):
    """``(name, period of (mixer, ffn) kinds, G)`` for ``lead`` and
    ``main``."""
    lead = tuple((t, "dense") for t in s.layer_types[: s.n_dense])
    main = tuple((t, "moe") for t in s.layer_types[s.n_dense:])
    return [(name, *_period(k)) for name, k in (("lead", lead),
                                               ("main", main)) if k]


def weight_shapes(s: Shape) -> dict:
    D, V = s.d, s.vocab
    qd, kvd, hd = s.heads * s.head_dim, s.kv_heads * s.head_dim, s.head_dim
    Eh = s.held[1] - s.held[0]
    out = {"embed": (V, D), "norm_f_w": (D,)}
    for name, kinds, G in parts(s):
        for j, (mixer, ffn) in enumerate(kinds):
            shp = {"op_norm": (D,), "ffn_norm": (D,)}
            if mixer == "conv":
                shp.update(conv_in=(D, 3 * D), conv_w=(s.conv_len, D),
                           conv_out=(D, D))
            else:
                shp.update(wq=(D, qd), wk=(D, kvd), wv=(D, kvd), wo=(qd, D),
                           q_norm=(hd,), k_norm=(hd,))
            if ffn == "dense":
                shp.update(w1=(D, s.ff), w3=(D, s.ff), w2=(s.ff, D))
            else:
                F = s.expert_ff
                shp.update(router=(D, s.experts), router_bias=(s.experts,),
                           ew1=(Eh, D, F), ew3=(Eh, D, F), ew2=(Eh, F, D))
            out.update({f"{name}{j}_{k}": (G, *v) for k, v in shp.items()})
    return out


def _scale(name: str, shape: tuple, s: Shape) -> tuple:
    """(mean, std) of each weight: matrices 1/sqrt(fan_in), the embedding
    0.02, norm weights about 1, conv taps 1/sqrt(taps), the selection bias
    ``BIAS_STD``."""
    if name == "embed":
        return 0.0, 0.02
    if name.endswith("norm") or name == "norm_f_w":
        return 1.0, 0.1
    if name.endswith("router_bias"):
        return 0.0, BIAS_STD
    if name.endswith("conv_w"):
        return 0.0, 1.0 / math.sqrt(s.conv_len)
    return 0.0, 1.0 / math.sqrt(shape[-2])


def init_weights(s: Shape, key, dtype) -> dict:
    """Every weight from ``key``, made on the device in ``dtype`` (one
    dtype, or a dict of one per weight). Call it under ``jax.jit`` so the
    whole set is one program."""
    shapes = weight_shapes(s)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shp) in zip(keys, sorted(shapes.items())):
        mean, std = _scale(name, shp, s)
        dt = dtype[name] if isinstance(dtype, dict) else dtype
        out[name] = (mean + std * jax.random.normal(k, shp, jnp.float32)
                     ).astype(dt)
    return out


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w


def _rope(x, theta: float):
    """Rotate-half RoPE over the whole head. x: (S, heads, hd)."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def int8_weights(b):
    """``b`` rounded to int8 per output column (symmetric, scale
    max|b| / 127 over the input axis), returned dequantized."""
    scale = jnp.max(jnp.abs(b), axis=-2, keepdims=True) / 127.0
    return jnp.round(b / scale) * scale


def route(h, router, bias, s: Shape):
    """(S, E) weight of every expert for every token: the top-k of the
    sigmoid scores plus the bias, weighted by their scores renormalized.
    The router's matmul is at ``HIGHEST`` in the reference and the control
    alike, as in the program (configuration's ``precision``)."""
    scores = jax.nn.sigmoid(jnp.matmul(h, router,
                                       precision=lax.Precision.HIGHEST))
    _, sel = lax.top_k(scores + bias, s.top_k)
    g = jnp.take_along_axis(scores, sel, axis=-1)
    g = g / (jnp.sum(g, -1, keepdims=True) + 1e-6) * s.routed_scale
    return jnp.sum(jax.nn.one_hot(sel, s.experts) * g[..., None], axis=1)


def forward(w: dict, s: Shape, tokens, *, precision=lax.Precision.HIGHEST,
            int8: bool = False):
    """Logits ``(S, V)`` for one sequence ``tokens (S,)``, in float32 with
    matmul ``precision``. With ``int8`` every matmul weight (experts,
    router and the tied unembedding included) is first rounded to int8."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    quant = int8_weights if int8 else (lambda b: b)

    def mm(a, b):
        return jnp.matmul(a, quant(f32(b)), precision=precision)

    S = tokens.shape[0]
    H, K, hd, L = s.heads, s.kv_heads, s.head_dim, s.conv_len
    lo, hi = s.held
    x = f32(jnp.take(w["embed"], tokens, axis=0))
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    kv_of_q = jnp.arange(H) // (H // K)

    def conv(h, p):
        b, c, u = jnp.split(mm(h, p["conv_in"]), 3, axis=-1)
        z = jnp.pad(b * u, ((L - 1, 0), (0, 0)))
        y = sum(f32(p["conv_w"])[i] * z[i:i + S] for i in range(L))
        return mm(c * y, p["conv_out"])

    def head(qkv):
        """Causal softmax attention of one head: (S, hd) each."""
        q, k, v = qkv
        scores = jnp.matmul(q, k.T, precision=precision) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.matmul(probs, v, precision=precision)

    def attn(h, p):
        q = _rms(mm(h, p["wq"]).reshape(S, H, hd), f32(p["q_norm"]), s.eps)
        k = _rms(mm(h, p["wk"]).reshape(S, K, hd), f32(p["k_norm"]), s.eps)
        q, k = _rope(q, s.rope_theta), _rope(k, s.rope_theta)[:, kv_of_q]
        v = mm(h, p["wv"]).reshape(S, K, hd)[:, kv_of_q]
        att = lax.map(head, tuple(a.transpose(1, 0, 2) for a in (q, k, v)))
        return mm(att.transpose(1, 0, 2).reshape(S, H * hd), p["wo"])

    def dense(h, p):
        return mm(jax.nn.silu(mm(h, p["w1"])) * mm(h, p["w3"]), p["w2"])

    def experts(h, p):
        """Every held expert over every token, weighted by its gate (0 where
        the token did not choose it), one expert at a time."""
        gate = route(h, quant(f32(p["router"])), f32(p["router_bias"]),
                     s)[:, lo:hi]

        def one(y, e):
            u = jax.nn.silu(mm(h, p["ew1"][e])) * mm(h, p["ew3"][e])
            return y + gate[:, e, None] * mm(u, p["ew2"][e]), None
        y, _ = lax.scan(one, jnp.zeros_like(h), jnp.arange(hi - lo))
        return y

    for name, kinds, _ in parts(s):
        def period(x, p, name=name, kinds=kinds):
            for j, (mixer, ffn) in enumerate(kinds):
                pre = f"{name}{j}_"
                q = {k[len(pre):]: v for k, v in p.items()
                     if k.startswith(pre)}
                h = _rms(x, f32(q["op_norm"]), s.eps)
                x = x + (conv(h, q) if mixer == "conv" else attn(h, q))
                h = _rms(x, f32(q["ffn_norm"]), s.eps)
                x = x + (dense(h, q) if ffn == "dense" else experts(h, q))
            return x, None

        x, _ = lax.scan(period, x, {k: v for k, v in w.items()
                                    if k.startswith(name)})
    x = _rms(x, f32(w["norm_f_w"]), s.eps)
    return mm(x, w["embed"].T)


def _gaps(ref, pick, targets):
    """Where ``targets >= 0``: how far the reference's logit of ``pick``
    lies below its best, and the reference's own margin (best minus second
    best). Both 0 elsewhere."""
    got = jnp.take_along_axis(ref, jnp.maximum(pick, 0)[:, None], 1)[:, 0]
    top2 = lax.top_k(ref, 2)[0]
    served = targets >= 0
    return (jnp.where(served, top2[:, 0] - got, 0.0),
            jnp.where(served, top2[:, 0] - top2[:, 1], 0.0))


def served_gaps(w: dict, s: Shape, tokens, targets):
    """Per position p, how far the reference's logit of ``targets[p]`` (the
    token served after position p) lies below the reference's best logit,
    and the reference's margin there. ``tokens`` is prompt + served tokens,
    padded; ``targets`` is -1 where nothing was served. Returns two (S,)
    float32 arrays, 0 where targets is -1."""
    return _gaps(forward(w, s, tokens), targets, targets)


def control_gaps(w: dict, s: Shape, tokens, targets):
    """The control: the reference put in the program's place one precision
    step below it. The program computes its matmuls on bfloat16 operands
    (the TPU's default precision for float32); the control rounds every
    matmul weight to int8 per output column and otherwise computes as the
    program does. At the same served positions as ``served_gaps``, how far
    the reference's logit of the token the control puts first lies below
    the reference's best, and the reference's margin."""
    ref = forward(w, s, tokens)
    low = forward(w, s, tokens, precision=lax.Precision.DEFAULT, int8=True)
    return _gaps(ref, jnp.argmax(low, axis=-1), targets)
