"""Unified model API: one entry point per family.

Usage:
    lm = LM(cfg)
    params = lm.init(key, dtype)
    logits, aux = lm.forward_train(params, batch)   # teacher forcing
    cache = lm.init_cache(batch_size, max_len)
    logits, cache = lm.prefill(params, batch, cache)
    logits, cache = lm.decode_step(params, token, cache)

A sliding window, where a config has one, is ``cfg.window``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import rglru, transformer, whisper, xlstm

_FAMILY_MODULES = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "audio": whisper,
    "ssm": xlstm,
    "hybrid": rglru,
}


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.mod = _FAMILY_MODULES[cfg.family]

    # -- params / cache ------------------------------------------------------
    def init(self, key, dtype=jnp.float32):
        return self.mod.init_params(self.cfg, key, dtype)

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return self.mod.init_cache(self.cfg, batch, max_len, dtype)

    # -- forward passes ------------------------------------------------------
    def forward_train(self, params, batch):
        return self.mod.forward_train(params, self.cfg, batch)

    def prefill(self, params, batch, cache):
        return self.mod.prefill(params, self.cfg, batch, cache)

    def decode_step(self, params, token, cache):
        return self.mod.decode_step(params, self.cfg, token, cache)


def make_demo_batch(cfg: ModelConfig, batch: int, seq: int, key,
                    dtype=jnp.float32):
    """Concrete random batch for smoke tests / examples."""
    k1, k2 = jax.random.split(key)
    out = {
        "tokens": jax.random.randint(k1, (batch, seq), 0, cfg.vocab_size),
    }
    if cfg.is_encoder_decoder:
        out["enc_feats"] = jax.random.normal(
            k2, (batch, cfg.encoder_seq, cfg.d_model), dtype) * 0.02
    return out
