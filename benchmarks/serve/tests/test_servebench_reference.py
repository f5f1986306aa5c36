"""The engine's prefill -> KV ring -> insert into a reused decode slot ->
batched decode agrees with the plain f32 reference, at small sizes on the
CPU, for both configurations."""
import numpy as np
import pytest

from servebench_small import run, small_config  # noqa: F401

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


class Capture:
    """``on_decode`` hook keeping each live request's logits row."""

    def __init__(self, inner):
        self.inner = inner
        self.rows = {}

    def __call__(self, active, logits):
        self.inner(active, logits)
        for slot, req in active.items():
            self.rows.setdefault(req.rec.rid, []).append(
                (len(req.generated), np.asarray(logits[slot])))


@pytest.mark.parametrize("name", ["granite_3_8b", "starcoder2_15b"])
def test_engine_matches_reference(name):
    c = small_config(name, slots=2)
    sess = run.Session(c, seed=2**32 + 5)
    cap = Capture(sess.recorder)
    sess.eng.on_decode = cap
    rng = np.random.default_rng(0)
    lens = [(20, 9), (13, 6), (31, 7), (8, 5)]      # 4 requests, 2 slots
    prompts = [rng.integers(0, c["vocab_size"], p).astype(np.int32)
               for p, _ in lens]
    for p, (_, n) in zip(prompts, lens):
        sess.eng.submit(p, n, 0.0)
    sess.eng.run()
    fwd = jax.jit(lambda w, t: sess.ref.forward(w, sess.shape, t))
    done = {r.rec.rid: r for r in sess.eng.finished}
    assert sorted(done) == [0, 1, 2, 3]
    slots_used = {}
    for rid, (p, (P, n)) in enumerate(zip(prompts, lens)):
        req = done[rid]
        assert len(req.generated) == n
        slots_used.setdefault(req.slot, []).append(rid)
        seq = np.concatenate([p, np.asarray(req.generated[:-1], np.int32)])
        ref = np.asarray(fwd(sess.weights, jnp.asarray(seq)))
        # the first token comes from prefill: the reference's best there
        assert req.generated[0] == int(ref[P - 1].argmax())
        rows = cap.rows[rid]
        assert len(rows) == n - 1
        scale = np.abs(ref).max()
        for g, row in rows:
            np.testing.assert_allclose(row, ref[P + g - 1], rtol=0,
                                       atol=1e-4 * scale)
            assert req.generated[g] == int(row.argmax())
    assert any(len(v) > 1 for v in slots_used.values()), \
        "no decode slot was reused"
