"""End-to-end system behaviour: the full paper pipeline in miniature —
serve a model's weights disaggregated under RAPID control, and check that
power-aware scheduling beats static under the paper's workload shape."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.controller import ControllerConfig, policy_4p4d
from repro.core.simulator import NodeSimulator, Workload
from repro.serving.engine import DisaggEngine


def test_train_then_serve_end_to_end(rng):
    """Weights handed to the engine after it is built, as the benchmark
    hands in its own, are the weights it serves: every request finishes,
    and each first token is the handed weights' greedy choice."""
    cfg = get_config("qwen1_5_4b").reduced()
    eng = DisaggEngine(cfg, n_prefill=1, n_decode=1, max_len=48,
                       decode_slots=2)
    params = eng.lm.init(jax.random.key(11), jnp.float32)
    eng.params = params
    for _ in range(3):
        eng.submit(rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
                   6, 0.0)
    s = eng.run()
    assert s.n_finished == 3
    for req in eng.finished:
        logits, _ = eng.lm.prefill(
            params, {"tokens": jnp.asarray(req.tokens)[None]},
            eng.lm.init_cache(1, 48, dtype=jnp.float32))
        assert req.generated[0] == int(jnp.argmax(logits[0]))


def test_rapid_improves_peak_load_slo():
    """Headline claim: up to ~2x SLO attainment at peak vs static."""
    cfg = get_config("llama3.1-8b")
    ctrl = dataclasses.replace(ControllerConfig(), allow_power=True,
                               allow_gpu=True)
    wl = Workload.sonnet_phases(6.5, seed=5, n1=300, n2=300)
    s_static = NodeSimulator(cfg, policy_4p4d(600)).run(wl)
    wl = Workload.sonnet_phases(6.5, seed=5, n1=300, n2=300)
    s_dyn = NodeSimulator(cfg, policy_4p4d(600), ctrl_cfg=ctrl).run(wl)
    assert s_dyn.slo_attainment >= 1.5 * s_static.slo_attainment
