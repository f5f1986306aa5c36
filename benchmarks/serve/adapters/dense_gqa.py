"""The program's side of a dense GQA configuration: the program's
``ModelConfig`` built from the configuration file, and the benchmark's
weights (``reference/dense_gqa.py`` layout) placed in the program's
parameter tree without a copy."""
from __future__ import annotations

import math

import jax

NEUTRAL = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
           "logits_scaling": 1.0, "tie_word_embeddings": False,
           "use_bias": False}


def program_config(c: dict):
    """``repro.configs.base.ModelConfig`` for configuration file ``c``.
    Refuses a file whose equations the program does not compute."""
    from repro.configs.base import ModelConfig
    for key, want in NEUTRAL.items():
        if key in c and c[key] != want:
            raise ValueError(f"{c['name']}: the program serves {key}={want}, "
                             f"the file states {c[key]}")
    if "attention_multiplier" in c and not math.isclose(
            c["attention_multiplier"], 1 / math.sqrt(c["head_dim"])):
        raise ValueError(f"{c['name']}: the program scales attention by "
                         f"1/sqrt(head_dim)")
    layernorm = c.get("norm_type", "rms_norm") == "layer_norm"
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]),
        mlp="swiglu" if c["hidden_act"] == "silu" else "gelu",
        norm="layernorm" if layernorm else "rmsnorm",
        norm_eps=float(c.get("norm_epsilon", c.get("rms_norm_eps", 1e-5))),
        qkv_bias=bool(c.get("qkv_bias", c.get("attention_bias", False))))


def layout(w: dict) -> dict:
    """The arrays of ``w`` arranged as the program's parameter tree."""
    def norm(prefix):
        p = {"w": w[f"{prefix}_w"]}
        if f"{prefix}_b" in w:
            p["b"] = w[f"{prefix}_b"]
        return p
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
    attn.update({k: w[k] for k in ("bq", "bk", "bv") if k in w})
    ffn = {"wi": w["w_up"], "wo": w["w_down"]}
    if "w_gate" in w:
        ffn["wg"] = w["w_gate"]
    tree = {"embed": w["embed"], "unembed": w["unembed"],
            "final_norm": norm("norm_f"),
            "slots": ({"ln1": norm("norm1"), "ln2": norm("norm2"),
                       "attn": attn, "ffn": ffn},)}
    return tree


def to_program(w: dict, like) -> dict:
    """``layout(w)``, checked against ``like``, the program's own tree
    (arrays or shape structs): every leaf must match in shape and dtype."""
    tree = layout(w)
    want = jax.tree_util.tree_structure(like)
    got = jax.tree_util.tree_structure(tree)
    if want != got:
        raise ValueError(f"parameter tree differs from the program's:\n"
                         f"program {want}\nbenchmark {got}")
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(tree),
                            jax.tree_util.tree_leaves(like)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"{jax.tree_util.keystr(path)}: benchmark "
                             f"{a.shape} {a.dtype}, program {b.shape} "
                             f"{b.dtype}")
    return tree
