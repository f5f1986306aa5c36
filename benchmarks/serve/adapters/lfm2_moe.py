"""The program's side of an LFM2 MoE configuration: the program's
``ModelConfig`` built from the configuration file, and the benchmark's
weights (``reference/lfm2_moe.py`` layout) placed in the program's
parameter tree without a copy."""
from __future__ import annotations

import re

import jax

# the equations the program computes; any other value is refused
FIXED = {"model_type": "lfm2_moe", "conv_bias": False, "norm_topk_prob": True,
         "use_expert_bias": True, "routed_scaling_factor": 1,
         "tie_word_embeddings": True}
PART = {"lead": "lead", "main": "slots"}


def program_config(c: dict):
    """``repro.configs.base.ModelConfig`` for configuration file ``c``.
    Refuses a file whose equations the program does not compute."""
    from repro.configs.base import ModelConfig
    for key, want in FIXED.items():
        if c[key] != want:
            raise ValueError(f"{c['name']}: the program serves {key}={want}, "
                             f"the file states {c[key]}")
    lo, hi = c["experts_held"]
    if hi - lo != c["num_experts"]:
        raise ValueError(f"{c['name']}: num_experts counts the experts held")
    return ModelConfig(
        name=c["name"], family="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["moe_intermediate_size"], d_ff_dense=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        layer_types=tuple("attn" if t == "full_attention" else t
                          for t in c["layer_types"]),
        conv_width=c["conv_L_cache"], qk_norm=True,
        rope_theta=float(c["rope_parameters"]["rope_theta"]),
        n_experts=c["source_values"].get("num_experts", c["num_experts"]),
        top_k=c["num_experts_per_tok"], n_dense_layers=c["num_dense_layers"],
        router="sigmoid", experts_held=(lo, hi), tie_embeddings=True,
        norm_eps=float(c["norm_eps"]))


def _slot(w: dict, pre: str) -> dict:
    g = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
    p = {"ln1": {"w": g["op_norm"]}, "ln2": {"w": g["ffn_norm"]}}
    if "conv_in" in g:
        p["conv"] = {"w_in": g["conv_in"], "w_conv": g["conv_w"],
                     "w_out": g["conv_out"]}
    else:
        p["attn"] = {k: g[k] for k in ("wq", "wk", "wv", "wo", "q_norm",
                                       "k_norm")}
    if "router" in g:
        p["ffn"] = {"router": g["router"], "bias": g["router_bias"],
                    "wg": g["ew1"], "wi": g["ew3"], "wo": g["ew2"]}
    else:
        p["ffn"] = {"wg": g["w1"], "wi": g["w3"], "wo": g["w2"]}
    return p


def layout(w: dict) -> dict:
    """The arrays of ``w`` arranged as the program's parameter tree."""
    tree = {"embed": w["embed"], "final_norm": {"w": w["norm_f_w"]}}
    for part, name in PART.items():
        slots = sorted({int(m.group(1)) for k in w
                        if (m := re.match(rf"{part}(\d+)_op_norm$", k))})
        if slots:
            tree[name] = tuple(_slot(w, f"{part}{j}_") for j in slots)
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
