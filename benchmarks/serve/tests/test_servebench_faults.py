"""A whole run without the chip look, at small sizes on the CPU, with the
timed path broken underneath: ``correct`` must come out false for each
fault a serving cell can have, and true with none. (One chip: there is no
exchange between chips to leave out.)"""
import time

import jax.numpy as jnp
import pytest

from servebench_small import run, small_config, small_traffic

import repro.serving.engine as engine  # noqa: E402

CELL = "granite_3_8b.chat"


def stale_state(prefill, decode):
    """The decode step returns its cache unchanged."""
    def bad(p, tok, cache):
        nxt, logits, _ = decode(p, tok, cache)
        return nxt, logits, cache
    return prefill, bad


def altered_token(prefill, decode):
    """Each token is altered where the decode step produces it."""
    def bad(p, tok, cache):
        nxt, logits, cache = decode(p, tok, cache)
        return (nxt + 1) % logits.shape[-1], logits, cache
    return prefill, bad


def half_batch(prefill, decode):
    """Half of the batch left out: the second half of the slots get the
    first half's tokens."""
    def bad(p, tok, cache):
        nxt, logits, cache = decode(p, tok, cache)
        h = nxt.shape[0] // 2
        return jnp.concatenate([nxt[:h], nxt[:h]]), logits, cache
    return prefill, bad


def one_run(monkeypatch, fault=None, control=False, limits=None):
    if fault is not None:
        real = engine.build_steps
        monkeypatch.setattr(engine, "build_steps",
                            lambda lm: fault(*real(lm)))
    c = small_config("granite_3_8b")
    if limits is not None:
        c["check"] = dict(c["check"], limits=limits)
    return run.run(CELL, 2**31 + 99, 2.5, False, time.perf_counter(),
                   require_tpu=False, control=control,
                   overrides={"config": c,
                              # busy enough that every decode slot serves
                              "traffic": dict(small_traffic(CELL),
                                              rate_rps=20.0)})


def test_sound_run_is_correct(monkeypatch):
    res = one_run(monkeypatch)
    assert res["correct"] is True
    assert res["failed"] == 0
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert set(res["metrics"]) == {"itl_p99_ms", "setup_s"}
    assert list(res)[-1] == "checks"


def test_control_is_not_correct(monkeypatch):
    """The int8 control in the program's place, through the same check;
    on the CPU the program is exact, so the limits here are 0."""
    exact = {"mean_gap": 0.0, "gap_per_near_tie": 0.0}
    assert one_run(monkeypatch, limits=exact)["correct"] is True
    res = one_run(monkeypatch, control=True, limits=exact)
    assert res["correct"] is False
    assert res["failed"] == 0
    assert res["checks"]["mean_gap"]["value"] > 0.0
    assert res["checks"]["gap_per_near_tie"]["value"] > 0.0


@pytest.mark.parametrize("fault", [stale_state, altered_token, half_batch],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(monkeypatch, fault):
    res = one_run(monkeypatch, fault)
    assert res["correct"] is False
    assert any(chk["limit"] is not None and chk["value"] > chk["limit"]
               for chk in res["checks"].values())
