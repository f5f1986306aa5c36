"""Quickstart: build a small model from a config with random weights, then
generate greedily with the prefill/decode API the serving engine uses.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import LM


def main():
    cfg = get_config("qwen1.5-4b").reduced()     # 2-layer smoke variant
    print(f"model: {cfg.name} ({cfg.param_count()/1e6:.1f}M params)")

    lm = LM(cfg)
    params = lm.init(jax.random.key(0), jnp.float32)
    prompt = jnp.arange(12)[None, :] % cfg.vocab_size
    cache = lm.init_cache(1, 48, dtype=jnp.float32)
    logits, cache = lm.prefill(params, {"tokens": prompt}, cache)
    toks = [int(jnp.argmax(logits[0]))]
    for _ in range(10):
        logits, cache = lm.decode_step(params, jnp.asarray([toks[-1]]), cache)
        toks.append(int(jnp.argmax(logits[0])))
    print("generated token ids:", toks)


if __name__ == "__main__":
    main()
