"""Real-compute disaggregated engine: KV handoff through the ring buffer,
continuous batching with per-slot positions, exact token-level consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.controller import ControllerConfig
from repro.models import LM
from repro.serving.engine import DisaggEngine, build_steps


@pytest.mark.parametrize("arch", ["qwen1_5_4b", "xlstm_350m",
                                  "recurrentgemma_2b", "whisper_large_v3"])
def test_engine_serves_all_requests(arch, rng):
    cfg = get_config(arch).reduced()
    eng = DisaggEngine(cfg, n_prefill=1, n_decode=1, max_len=80,
                       decode_slots=3)
    for _ in range(5):
        eng.submit(rng.integers(0, cfg.vocab_size, 16).astype(np.int32),
                   8, 0.0)
    s = eng.run()
    assert s.n_finished == 5
    assert all(len(r.generated) == 8 for r in eng.finished)


def test_engine_tokens_match_single_request_decode(rng):
    """Continuous batching (mixed positions, slot insertion) must produce
    exactly the tokens of an isolated prefill+decode."""
    cfg = get_config("qwen1_5_4b").reduced()
    eng = DisaggEngine(cfg, n_prefill=1, n_decode=1, max_len=64,
                       decode_slots=3, seed=7)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (12, 20, 17)]
    for p in prompts:
        eng.submit(p, 8, 0.0)
    eng.run()
    lm = LM(cfg)
    for req in eng.finished:
        cache = lm.init_cache(1, 64, dtype=jnp.float32)
        lg, cache = lm.prefill(eng.params,
                               {"tokens": jnp.asarray(req.tokens)[None]},
                               cache)
        toks = [int(jnp.argmax(lg[0]))]
        for _ in range(len(req.generated) - 1):
            lg, cache = lm.decode_step(eng.params, jnp.asarray([toks[-1]]),
                                       cache)
            toks.append(int(jnp.argmax(lg[0])))
        assert toks == req.generated


def test_engine_with_controller_respects_budget(rng):
    cfg = get_config("qwen1_5_4b").reduced()
    ctrl = ControllerConfig(ttft_slo=0.01, tpot_slo=0.001, cooldown_s=0.1,
                            power_cooldown_s=0.02, allow_power=True,
                            allow_gpu=True)
    eng = DisaggEngine(cfg, n_prefill=2, n_decode=2, max_len=64,
                       decode_slots=3, ctrl_cfg=ctrl)
    for _ in range(8):
        eng.submit(rng.integers(0, cfg.vocab_size, 20).astype(np.int32),
                   6, 0.0)
    eng.run()
    assert sum(eng.pm.effective) <= eng.pm.budget + 1e-6


def test_ring_backpressure(rng):
    from repro.serving.ring import KVRing
    ring = KVRing(2)
    assert ring.try_put("a") is not None
    assert ring.try_put("b") is not None
    assert ring.try_put("c") is None       # full -> backpressure
    assert ring.try_pull() == "a"          # pull frees a slot, FIFO order
    assert ring.try_put("c") is not None


def test_step_module_names_are_stable():
    """The benchmark's trace reduction finds the engine's steps on the
    device by their module names."""
    cfg = get_config("granite_3_8b").reduced()
    lm = LM(cfg)
    prefill, decode = build_steps(lm)
    params = jax.eval_shape(lambda k: lm.init(k, jnp.float32),
                            jax.random.key(0))
    cache = jax.eval_shape(lambda: lm.init_cache(1, 32, dtype=jnp.float32))
    dcache = dict(jax.eval_shape(
        lambda: lm.init_cache(2, 32, dtype=jnp.float32)))
    dcache["pos"] = jax.ShapeDtypeStruct((2,), jnp.int32)
    toks = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    tok = jax.ShapeDtypeStruct((2,), jnp.int32)
    assert "module @jit_prefill " in prefill.lower(params, toks,
                                                   cache).as_text()
    assert "module @jit_decode " in decode.lower(params, tok,
                                                 dcache).as_text()
