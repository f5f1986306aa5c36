"""Useful work of the model's steps, from the configuration file and the
live arrays' dtypes: the yardstick for roofline shares and MFU.

FLOPs count the matmuls' parameters times tokens, plus attention over the
positions actually held (never the padded cache length). A prefill
needs the logits of its last position only. Bytes count the parameters
read (the embedding table only for the rows gathered) and the KV cache of
the live positions.
"""
from __future__ import annotations

from typing import Iterable


def layer_matmul_params(c: dict) -> int:
    D, F = c["hidden_size"], c["intermediate_size"]
    qd = c["num_attention_heads"] * c["head_dim"]
    kvd = c["num_key_value_heads"] * c["head_dim"]
    mlp = (3 if c["hidden_act"] == "silu" else 2) * D * F
    return D * qd + 2 * D * kvd + qd * D + mlp


def layer_other_params(c: dict) -> int:
    """Norm weights and biases of one layer: read, but no matmul."""
    D = c["hidden_size"]
    n = 2 * D * (2 if c.get("norm_type") == "layer_norm" else 1)
    if c.get("qkv_bias", c.get("attention_bias", False)):
        n += (c["num_attention_heads"] + 2 * c["num_key_value_heads"]) \
            * c["head_dim"]
    return n


def _attn_flops_per_key(c: dict) -> int:
    """QK^T and PV for one query against one key, over all layers."""
    return 4 * c["num_attention_heads"] * c["head_dim"] * c["num_hidden_layers"]


def _token_flops(c: dict) -> int:
    return 2 * layer_matmul_params(c) * c["num_hidden_layers"]


def prefill_flops(c: dict, length: int) -> int:
    """One prompt of ``length`` tokens: every matmul on every token, causal
    attention, and the unembedding of the last position."""
    causal_pairs = length * (length + 1) // 2
    return (_token_flops(c) * length + _attn_flops_per_key(c) * causal_pairs
            + 2 * c["hidden_size"] * c["vocab_size"])


def decode_flops(c: dict, keys: Iterable[int]) -> int:
    """One decode step; ``keys`` holds, per live slot, the positions it
    attends to (its new one included)."""
    keys = list(keys)
    per = _token_flops(c) + 2 * c["hidden_size"] * c["vocab_size"]
    return per * len(keys) + _attn_flops_per_key(c) * sum(keys)


def kv_bytes_per_position(c: dict, kv_itemsize: int) -> int:
    return (2 * c["num_key_value_heads"] * c["head_dim"]
            * c["num_hidden_layers"] * kv_itemsize)


def decode_bytes(c: dict, keys: Iterable[int], param_itemsize: int,
                 kv_itemsize: int) -> int:
    """One decode step: every layer's weights, the unembedding, the final
    norm, the gathered embedding rows, and the KV of the live positions."""
    keys = list(keys)
    D, V, L = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    norm_f = D * (2 if c.get("norm_type") == "layer_norm" else 1)
    weights = (L * (layer_matmul_params(c) + layer_other_params(c))
               + D * V + norm_f + D * len(keys))
    return (weights * param_itemsize
            + kv_bytes_per_position(c, kv_itemsize) * sum(keys))


def least_time(flops: float, nbytes: float, peak: dict):
    """(seconds, bound): the larger of compute and memory time at peak."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
