"""Prefill+decode must reproduce teacher-forced logits for every family,
including sliding-window attention, dropless-MoE, recurrent state handoff."""
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


@pytest.mark.parametrize("arch,window,moe", CASES)
def test_prefill_decode_matches_teacher_forcing(arch, window, moe):
    cfg = get_config(arch).reduced()
    assert bool(cfg.n_experts) == moe      # every MoE layer is dropless
    lm = LM(cfg)
    key = jax.random.key(1)
    params = lm.init(key)
    B, S, P = 2, 24, 16
    batch = make_demo_batch(cfg, B, S, key)
    full, _ = lm.forward_train(params, batch, remat=False, window=window)
    cache = lm.init_cache(B, S + 4, dtype=jnp.float32, window=window)
    pb = dict(batch)
    pb["tokens"] = batch["tokens"][:, :P]
    lg, cache = lm.prefill(params, pb, cache, window=window)
    errs = [float(jnp.max(jnp.abs(lg - full[:, P - 1])))]
    for t in range(P, S):
        lg, cache = lm.decode_step(params, batch["tokens"][:, t], cache,
                                   window=window)
        errs.append(float(jnp.max(jnp.abs(lg - full[:, t]))))
    assert max(errs) < 2e-3, (arch, errs)


def test_vector_pos_decode_matches_scalar():
    """Per-slot positions (continuous batching) == scalar-pos decode."""
    cfg = get_config("qwen1_5_4b").reduced()
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
