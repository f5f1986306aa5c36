"""Useful work of an LFM2 MoE configuration's steps, from the configuration
file and the live arrays' dtypes: the yardstick for its roofline shares,
MFU and the ``moe_gmm`` kernel's roofline.

FLOPs count the matmuls' parameters times tokens, plus attention over the
positions actually held (never the padded cache length). Routed experts
count at the expected share: each token's ``top_k`` experts land on the
experts held here with probability ``held / routed``, so a token costs
``top_k * held / routed`` experts' matmuls here (16 of 64 experts, top-4:
one expert a token). A prefill needs the logits of its last position only.

Bytes are exact: every weight once per step (each held expert once per
``moe_gmm`` call; the tied embedding once, as the unembedding, plus the
rows gathered), the KV of the live positions of the attention layers, and
the convolution state of the live slots.
"""
from __future__ import annotations

from typing import Iterable


def _kinds(c: dict):
    n = c["num_dense_layers"]
    return [(t, "dense" if i < n else "moe")
            for i, t in enumerate(c["layer_types"])]


def _held(c: dict) -> int:
    lo, hi = c["experts_held"]
    return hi - lo


def _routed(c: dict) -> int:
    return c["source_values"].get("num_experts", c["num_experts"])


def _mixer_params(c: dict, mixer: str) -> int:
    D, hd = c["hidden_size"], c["head_dim"]
    if mixer == "conv":
        return 3 * D * D + D * D
    qd, kvd = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return D * qd + 2 * D * kvd + qd * D


def expert_params(c: dict) -> int:
    """Matmul parameters of one expert."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def param_count(c: dict) -> int:
    """Every parameter the program holds for configuration file ``c``."""
    D = c["hidden_size"]
    total = c["vocab_size"] * D + D
    for mixer, ffn in _kinds(c):
        total += 2 * D + _mixer_params(c, mixer)
        total += c["conv_L_cache"] * D if mixer == "conv" else \
            2 * c["head_dim"]
        if ffn == "dense":
            total += 3 * D * c["intermediate_size"]
        else:
            total += D * _routed(c) + _routed(c) + _held(c) * expert_params(c)
    return total


def _token_flops(c: dict) -> float:
    """Matmul FLOPs of one token through every layer, routed experts at
    their expected share here."""
    D = c["hidden_size"]
    share = c["num_experts_per_tok"] * _held(c) / _routed(c)
    n = 0.0
    for mixer, ffn in _kinds(c):
        n += _mixer_params(c, mixer)
        n += (3 * D * c["intermediate_size"] if ffn == "dense" else
              D * _routed(c) + share * expert_params(c))
    return 2 * n


def _attn_flops_per_key(c: dict) -> int:
    """QK^T and PV for one query against one key, over all attention
    layers."""
    n_attn = sum(1 for t in c["layer_types"] if t == "full_attention")
    return 4 * c["num_attention_heads"] * c["head_dim"] * n_attn


def prefill_flops(c: dict, length: int) -> float:
    causal_pairs = length * (length + 1) // 2
    return (_token_flops(c) * length + _attn_flops_per_key(c) * causal_pairs
            + 2 * c["hidden_size"] * c["vocab_size"])


def decode_flops(c: dict, keys: Iterable[int]) -> float:
    """One decode step; ``keys`` holds, per live slot, the positions it
    attends to (its new one included)."""
    keys = list(keys)
    per = _token_flops(c) + 2 * c["hidden_size"] * c["vocab_size"]
    return per * len(keys) + _attn_flops_per_key(c) * sum(keys)


def kv_bytes_per_position(c: dict, kv_itemsize: int) -> int:
    n_attn = sum(1 for t in c["layer_types"] if t == "full_attention")
    return (2 * c["num_key_value_heads"] * c["head_dim"] * n_attn
            * kv_itemsize)


def conv_state_bytes(c: dict, itemsize: int) -> int:
    """One slot's convolution state, over all conv layers."""
    n_conv = sum(1 for t in c["layer_types"] if t == "conv")
    return n_conv * (c["conv_L_cache"] - 1) * c["hidden_size"] * itemsize


def decode_bytes(c: dict, keys: Iterable[int], param_itemsize: int,
                 kv_itemsize: int) -> int:
    """One decode step: every parameter (the embedding table once, as the
    unembedding), the gathered embedding rows, the KV of the live
    positions and the live slots' convolution state."""
    keys = list(keys)
    weights = param_count(c) + c["hidden_size"] * len(keys)
    return (weights * param_itemsize
            + kv_bytes_per_position(c, kv_itemsize) * sum(keys)
            + conv_state_bytes(c, kv_itemsize) * len(keys))


def moe_gmm_ops(c: dict) -> int:
    """The ``moe_gmm`` ops of a decode program: one per MoE slot of the
    main stack's shortest period, since the program scans over whole
    periods (``models/transformer.py``)."""
    kinds = _kinds(c)[c["num_dense_layers"]:]
    n = len(kinds)
    p = next(p for p in range(1, n + 1)
             if n % p == 0 and kinds == kinds[:p] * (n // p))
    return sum(1 for _, f in kinds[:p] if f == "moe")


def moe_gmm_calls(c: dict, decode_slots: int, itemsize: int):
    """The ``moe_gmm`` calls of one decode step, as (FLOPs, bytes) each:
    per MoE layer the gate, up and down matmuls over the step's
    ``decode_slots * top_k`` rows. Bytes are exact (every held expert's
    weight once, the rows in once, the rows out once); FLOPs are the
    expected rows routed here, live slot or not."""
    D, F = c["hidden_size"], c["moe_intermediate_size"]
    E, k = _held(c), c["num_experts_per_tok"]
    M = decode_slots * k
    rows = M * E / _routed(c)
    n_moe = sum(1 for _, f in _kinds(c) if f == "moe")
    calls = []
    for K, N in ((D, F), (D, F), (F, D)):
        calls += [(2 * rows * K * N,
                   itemsize * (E * K * N + M * K + M * N))] * n_moe
    return calls
