"""Per-assigned-architecture smoke tests: reduced variant of each family on
the CPU, its teacher-forcing forward and its serving path, asserting output
shapes and no NaNs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import LM, make_demo_batch

B, S = 2, 24


@pytest.fixture(scope="module")
def key():
    return jax.random.key(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_train_step(arch, key):
    """The full-sequence forward (``forward_train``): logits for every
    position, finite, and a finite MoE load-balancing statistic (0 for a
    model without experts)."""
    cfg = get_config(arch).reduced()
    lm = LM(cfg)
    params = lm.init(key)
    batch = make_demo_batch(cfg, B, S, key)

    logits, aux = lm.forward_train(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert aux.shape == () and bool(jnp.isfinite(aux))
    if not cfg.n_experts:
        assert float(aux) == 0.0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_serve_path_shapes(arch, key):
    cfg = get_config(arch).reduced()
    lm = LM(cfg)
    params = lm.init(key)
    batch = make_demo_batch(cfg, B, 16, key)
    cache = lm.init_cache(B, 32, dtype=jnp.float32)
    logits, cache = lm.prefill(params, batch, cache)
    assert logits.shape == (B, cfg.vocab_size)
    assert not jnp.isnan(logits).any()
    logits, cache = lm.decode_step(params, jnp.argmax(logits, -1), cache)
    assert logits.shape == (B, cfg.vocab_size)
    assert not jnp.isnan(logits).any()
