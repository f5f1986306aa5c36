"""Plain reference for a dense GQA decoder, written from the configuration
file's statement of the equations (``equations`` in ``configs/*.json``).

It imports nothing of the program under test. Weights are the benchmark's
own, made here from a seed; the harness hands the same arrays to the
program in the program's layout.

Layout (stacked over layers, leading axis ``L``): ``embed (V, D)``,
``unembed (D, V)``, ``norm_f_w/b (D)``, ``norm1_w/b``, ``norm2_w/b (L, D)``,
``wq (L, D, H*hd)``, ``wk``/``wv (L, D, K*hd)``, ``bq``/``bk``/``bv`` (when
the config has QKV biases), ``wo (L, H*hd, D)``, ``w_up (L, D, F)``,
``w_gate (L, D, F)`` (gated MLP only), ``w_down (L, F, D)``. Norm biases
exist only for LayerNorm.

``forward`` runs the whole sequence without a cache, one layer at a time
under ``lax.scan``, in float32: at ``HIGHEST`` matmul precision it is the
reference; ``control_gaps`` runs it at ``DEFAULT`` with int8 weights.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


class Shape(NamedTuple):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    gated: bool            # SwiGLU (silu gate * up) when True, else GeLU-tanh
    layernorm: bool        # LayerNorm with bias when True, else RMSNorm
    qkv_bias: bool
    eps: float
    rope_theta: float


def shape_of(c: dict) -> Shape:
    """The reference's view of a configuration file."""
    act = c["hidden_act"]
    if act not in ("silu", "gelu_pytorch_tanh"):
        raise ValueError(f"no reference for hidden_act {act!r}")
    layernorm = c.get("norm_type", "rms_norm") == "layer_norm"
    return Shape(
        layers=c["num_hidden_layers"], d=c["hidden_size"],
        heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], ff=c["intermediate_size"],
        vocab=c["vocab_size"], gated=act == "silu", layernorm=layernorm,
        qkv_bias=bool(c.get("qkv_bias", c.get("attention_bias", False))),
        eps=float(c.get("norm_epsilon", c.get("rms_norm_eps", 1e-5))),
        rope_theta=float(c["rope_theta"]))


def weight_shapes(s: Shape) -> dict:
    L, D, F, V = s.layers, s.d, s.ff, s.vocab
    qd, kvd = s.heads * s.head_dim, s.kv_heads * s.head_dim
    out = {"embed": (V, D), "unembed": (D, V), "norm_f_w": (D,),
           "norm1_w": (L, D), "norm2_w": (L, D),
           "wq": (L, D, qd), "wk": (L, D, kvd), "wv": (L, D, kvd),
           "wo": (L, qd, D), "w_up": (L, D, F), "w_down": (L, F, D)}
    if s.gated:
        out["w_gate"] = (L, D, F)
    if s.layernorm:
        out.update(norm_f_b=(D,), norm1_b=(L, D), norm2_b=(L, D))
    if s.qkv_bias:
        out.update(bq=(L, qd), bk=(L, kvd), bv=(L, kvd))
    return out


def _scale(name: str, shape: tuple) -> tuple:
    """(mean, std) of each weight: matrices 1/sqrt(fan_in), the embedding
    0.02, norm weights about 1, biases small."""
    if name == "embed":
        return 0.0, 0.02
    if name.endswith("_w") and name.startswith("norm"):
        return 1.0, 0.1
    if name.startswith("norm") or name in ("bq", "bk", "bv"):
        return 0.0, 0.1
    return 0.0, 1.0 / math.sqrt(shape[-2])


def init_weights(s: Shape, key, dtype) -> dict:
    """Every weight from ``key``, made on the device in ``dtype`` (one
    dtype, or a dict of one per weight). Call it under ``jax.jit`` so the
    whole set is one program."""
    shapes = weight_shapes(s)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shp) in zip(keys, sorted(shapes.items())):
        mean, std = _scale(name, shp)
        dt = dtype[name] if isinstance(dtype, dict) else dtype
        out[name] = (mean + std * jax.random.normal(k, shp, jnp.float32)
                     ).astype(dt)
    return out


def _norm(x, w, b, s: Shape):
    if s.layernorm:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + s.eps) * w + b
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + s.eps) * w


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


def forward(w: dict, s: Shape, tokens, *, precision=lax.Precision.HIGHEST,
            int8: bool = False):
    """Logits ``(S, V)`` for one sequence ``tokens (S,)``, in float32 with
    matmul ``precision``. With ``int8`` every matmul weight is first
    rounded to int8."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    quant = int8_weights if int8 else (lambda b: b)

    def mm(a, b):
        return jnp.matmul(a, quant(f32(b)), precision=precision)

    S = tokens.shape[0]
    H, K, hd = s.heads, s.kv_heads, s.head_dim
    x = f32(jnp.take(w["embed"], tokens, axis=0))
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    kv_of_q = jnp.arange(H) // (H // K)
    per_layer = {k: v for k, v in w.items()
                 if k not in ("embed", "unembed", "norm_f_w", "norm_f_b")}

    def layer(x, p):
        vec = {k: f32(v) for k, v in p.items() if v.ndim == 1}
        h = _norm(x, vec["norm1_w"], vec.get("norm1_b"), s)
        q, k, v = mm(h, p["wq"]), mm(h, p["wk"]), mm(h, p["wv"])
        if s.qkv_bias:
            q, k, v = q + vec["bq"], k + vec["bk"], v + vec["bv"]
        q = _rope(q.reshape(S, H, hd), s.rope_theta)
        k = _rope(k.reshape(S, K, hd), s.rope_theta)[:, kv_of_q]
        v = v.reshape(S, K, hd)[:, kv_of_q]              # (S, H, hd)
        scores = jnp.einsum("shd,thd->hst", q, k, precision=precision)
        scores = jnp.where(causal[None], scores / math.sqrt(hd), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        att = jnp.einsum("hst,thd->shd", probs, v, precision=precision)
        x = x + mm(att.reshape(S, H * hd), p["wo"])
        h = _norm(x, vec["norm2_w"], vec.get("norm2_b"), s)
        if s.gated:
            u = jax.nn.silu(mm(h, p["w_gate"])) * mm(h, p["w_up"])
        else:
            u = jax.nn.gelu(mm(h, p["w_up"]), approximate=True)
        return x + mm(u, p["w_down"]), None

    x, _ = lax.scan(layer, x, per_layer)
    x = _norm(x, f32(w["norm_f_w"]),
              f32(w["norm_f_b"]) if "norm_f_b" in w else None, s)
    return mm(x, w["unembed"])


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
