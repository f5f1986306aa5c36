"""Prefill+decode must reproduce teacher-forced logits for every family,
including sliding-window attention, dropless-MoE, recurrent state handoff."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import LM, make_demo_batch

CASES = [
    ("qwen1_5_4b", None, False),
    ("starcoder2_15b", None, False),
    ("phi3_5_moe", None, True),
    ("llama4_maverick", None, True),
    ("xlstm_350m", None, False),
    ("recurrentgemma_2b", None, False),
    ("whisper_large_v3", None, False),
    ("chameleon_34b", 8, False),
    ("granite_3_8b", 8, False),
    ("lfm2_24b_a2b", None, True),
]


# The reduced configs' head_dim is 64, so their KV caches take the
# lane-dense (B, S, K * hd) layout; at 128 they keep (B, S, K, hd).
SPLIT_KV_CASES = [
    ("granite_3_8b", None),
    ("granite_3_8b", 8),
    ("recurrentgemma_2b", None),
    ("whisper_large_v3", None),
    ("lfm2_24b_a2b", None),
]


def _at_head_dim_128(cfg):
    return dataclasses.replace(cfg, head_dim=128)


def _check_teacher_forcing(cfg, window):
    if window is not None:
        cfg = dataclasses.replace(cfg, window=window)
    lm = LM(cfg)
    key = jax.random.key(1)
    params = lm.init(key)
    B, S, P = 2, 24, 16
    batch = make_demo_batch(cfg, B, S, key)
    full, _ = lm.forward_train(params, batch)
    cache = lm.init_cache(B, S + 4, dtype=jnp.float32)
    pb = dict(batch)
    pb["tokens"] = batch["tokens"][:, :P]
    lg, cache = lm.prefill(params, pb, cache)
    errs = [float(jnp.max(jnp.abs(lg - full[:, P - 1])))]
    for t in range(P, S):
        lg, cache = lm.decode_step(params, batch["tokens"][:, t], cache)
        errs.append(float(jnp.max(jnp.abs(lg - full[:, t]))))
    assert max(errs) < 2e-3, (cfg.name, errs)


@pytest.mark.parametrize("arch,window,moe", CASES)
def test_prefill_decode_matches_teacher_forcing(arch, window, moe):
    cfg = get_config(arch).reduced()
    assert bool(cfg.n_experts) == moe      # every MoE layer is dropless
    _check_teacher_forcing(cfg, window)


@pytest.mark.parametrize("arch,window", SPLIT_KV_CASES)
def test_prefill_decode_matches_teacher_forcing_split_kv(arch, window):
    _check_teacher_forcing(_at_head_dim_128(get_config(arch).reduced()),
                           window)


def _check_vector_pos(cfg):
    lm = LM(cfg)
    key = jax.random.key(3)
    params = lm.init(key)
    B, P = 2, 12
    batch = make_demo_batch(cfg, B, P, key)
    cache = lm.init_cache(B, 24, dtype=jnp.float32)
    lg_s, cache_s = lm.prefill(params, batch, cache)
    tok = jnp.argmax(lg_s, -1)
    lg1, _ = lm.decode_step(params, tok, cache_s)
    cache_v = dict(cache_s)
    cache_v["pos"] = jnp.full((B,), P, jnp.int32)
    lg2, _ = lm.decode_step(params, tok, cache_v)
    assert float(jnp.max(jnp.abs(lg1 - lg2))) < 1e-5


def test_vector_pos_decode_matches_scalar():
    """Per-slot positions (continuous batching) == scalar-pos decode."""
    _check_vector_pos(get_config("qwen1_5_4b").reduced())


def test_vector_pos_decode_matches_scalar_split_kv():
    """The same at head_dim 128, where the cache keeps (B, S, K, hd)."""
    _check_vector_pos(_at_head_dim_128(get_config("qwen1_5_4b").reduced()))
