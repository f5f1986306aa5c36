"""LFM2 MoE through the engine against ``reference/lfm2_moe.py``, at a small
size on the CPU: width 64, 4 query / 2 KV heads, 8 routed experts of
which 4 are held, top-2, 6 layers (2 leading dense conv layers, then one
period of attention, conv, conv, conv).

On the CPU every program matmul is a plain float32 matmul, so program and
reference differ only in the order of float32 sums: each tolerance below
is a few float32 roundings of the values compared."""
import dataclasses
import time

import numpy as np
import pytest

from servebench_small import run, small_traffic
from test_servebench_reference import Capture

from servebench import spec, work_lfm2_moe  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import repro.serving.engine as engine  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models.moe_layer import init_moe, moe_forward, route  # noqa: E402

CELL = "lfm2_24b_a2b.batch"
EXACT = {"mean_gap": 0.0}


def small_lfm2(slots: int = 4, max_len: int = 96) -> dict:
    c = spec.config("lfm2_24b_a2b")
    c.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             vocab_size=256, num_hidden_layers=6,
             layer_types=c["layer_types"][:6], num_experts=4,
             num_experts_per_tok=2, experts_held=[0, 4])
    c["source_values"] = dict(c["source_values"], num_experts=8)
    c["serving"] = dict(c["serving"], decode_slots=slots, max_len=max_len)
    c["check"] = dict(c["check"], sample_served_tokens=1000, limits=EXACT)
    return c


def program_cfg(**kw):
    return dataclasses.replace(
        spec.module("adapters", "lfm2_moe").program_config(small_lfm2()),
        **kw)


def test_engine_matches_reference():
    """Prefill, the KV and conv state through the ring into a reused decode
    slot, batched decode at mixed positions: every served logit row equals
    the reference's full forward over the same tokens."""
    c = small_lfm2(slots=2)
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
        assert req.generated[0] == int(ref[P - 1].argmax())
        rows = cap.rows[rid]
        assert len(rows) == n - 1
        scale = np.abs(ref).max()
        for g, row in rows:
            np.testing.assert_allclose(row, ref[P + g - 1], rtol=0,
                                       atol=1e-5 * scale)
            assert req.generated[g] == int(row.argmax())
    assert any(len(v) > 1 for v in slots_used.values()), \
        "no decode slot was reused"


def test_expert_shares_sum_to_the_uncut_layer():
    """Two chips' shares of the 8 experts, each routing over all 8 and
    computing only its own, add up to the layer that holds all 8."""
    whole = program_cfg(experts_held=())
    p = init_moe(jax.random.key(1), whole, jnp.float32)
    p["bias"] = 0.3 * jax.random.normal(jax.random.key(2), p["bias"].shape)
    x = jax.random.normal(jax.random.key(3), (3, 7, whole.d_model))
    want, _ = moe_forward(p, x, whole)
    got = 0.0
    for lo, hi in ((0, 4), (4, 8)):
        share = {k: v[lo:hi] if k in ("wi", "wg", "wo") else v
                 for k, v in p.items()}
        part, _ = moe_forward(share, x,
                              program_cfg(experts_held=(lo, hi)))
        got = got + part
    # each token's two experts, added in another order
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-6 * float(jnp.abs(want).max()))


def test_selection_bias_picks_but_never_weighs():
    cfg = program_cfg()
    p = init_moe(jax.random.key(4), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(5), (64, cfg.d_model))
    g0, chosen0, scores = route(p, x, cfg)
    # a bias that moves every expert alike picks the same, weighs the same
    g1, chosen1, _ = route(dict(p, bias=p["bias"] + 0.7), x, cfg)
    assert (chosen1 == chosen0).all() and (g1 == g0).all()
    # a bias that favours expert 3 reorders the top-k of some tokens ...
    g2, chosen2, _ = route(dict(p, bias=p["bias"].at[3].set(0.5)), x, cfg)
    moved = (jnp.sort(chosen2, -1) != jnp.sort(chosen0, -1)).any(-1)
    assert 0 < int(moved.sum()) < 64
    assert (chosen2 == 3).any(-1).all()
    # ... and each chosen expert's weight is still its own score over the
    # chosen scores, the bias nowhere in it
    s = jnp.take_along_axis(scores, chosen2, -1)
    np.testing.assert_allclose(g2, s / (s.sum(-1, keepdims=True) + 1e-6),
                               rtol=1e-6)


def test_conv_decode_from_prefilled_state_equals_whole_sequence():
    cfg = program_cfg()
    p = L.init_conv(jax.random.key(6), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(7), (2, 11, cfg.d_model))
    whole, _ = L.conv_prefill(p, x, cfg)
    for P in (1, 2, 5):
        out, state = L.conv_prefill(p, x[:, :P], cfg)
        assert state.shape == (2, cfg.conv_width - 1, cfg.d_model)
        outs = [out]
        for t in range(P, x.shape[1]):
            o, state = L.conv_decode(p, x[:, t:t + 1], state)
            outs.append(o)
        # the same products and sums, in the same order
        np.testing.assert_allclose(jnp.concatenate(outs, 1), whole,
                                   rtol=0, atol=1e-6)


def drop_conv_state(insert):
    """The insert that leaves the slot's old convolution state in place."""
    def bad(family, dst, src, slot):
        out = insert(family, dst, src, slot)
        for name, slots in dst.items():
            out[name] = tuple(dict(new, **{"conv": old["conv"]})
                              if "conv" in old else new
                              for old, new in zip(slots, out[name]))
        return out
    return bad


def one_run(monkeypatch, *, control=False, fault=None):
    if fault is not None:
        monkeypatch.setattr(engine, "_cache_insert",
                            fault(engine._cache_insert))
    return run.run(CELL, 2**31 + 99, 2.5, False, time.perf_counter(),
                   require_tpu=False, control=control,
                   overrides={"config": small_lfm2(),
                              "traffic": small_traffic(CELL)})


@pytest.mark.parametrize("side", ["sound", "int8_control",
                                  "conv_state_dropped_at_insert"])
def test_sanity(monkeypatch, side):
    """The sound run is correct; the int8 control in the program's place,
    and a program that drops the conv state at insert, are not."""
    res = one_run(monkeypatch, control=side == "int8_control",
                  fault=drop_conv_state
                  if side == "conv_state_dropped_at_insert" else None)
    assert res["failed"] == 0
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert res["correct"] is (side == "sound")
    if side != "sound":
        assert res["checks"]["mean_gap"]["value"] > 0.0


def test_counted_parameter_bytes_are_the_placed_arrays():
    """The roofline's parameter bytes are those of the arrays the adapter
    places in the program for the cut configuration, and of the program's
    own tree: 14 layers, 16 experts held, f32."""
    c = spec.config("lfm2_24b_a2b")
    ref = spec.module("reference", "lfm2_moe")
    adapter = spec.module("adapters", "lfm2_moe")
    shape = ref.shape_of(c)
    w = jax.eval_shape(lambda k: ref.init_weights(shape, k, jnp.float32),
                       jax.random.key(0))
    placed = sum(a.size * a.dtype.itemsize
                 for a in jax.tree.leaves(adapter.layout(w)))
    from repro.models import LM
    lm = LM(adapter.program_config(c))
    own = jax.eval_shape(lambda k: lm.init(k, jnp.float32), jax.random.key(0))
    adapter.to_program(w, own)
    assert work_lfm2_moe.param_count(c) * 4 == placed
    # 9.23 GB: embedding 0.54, 2 dense conv layers 0.71, 12 MoE layers
    # 0.67 each
    assert 9.2e9 < placed < 9.3e9


def test_moe_gmm_roofline_reads_only_with_every_kernel_op():
    """The cell's decode program holds one ``moe_gmm`` op per MoE slot of a
    period (4); the reader sums them, and reads nothing when one of them
    is missing from the trace's largest ops or the kernel is absent."""
    from types import SimpleNamespace
    c = spec.config("lfm2_24b_a2b")
    assert work_lfm2_moe.moe_gmm_ops(c) == 4
    reader = spec.module("layer_metrics", "moe_gmm_roofline")
    peak = spec.peak("TPU v5 lite")

    def ctx(ops):
        trace = SimpleNamespace(
            top_ops=[("jit_decode/while.5", 9.0)] + ops,
            step_times=lambda word: [0.03] * 100)
        return SimpleNamespace(trace=trace, config=c, decode_slots=32,
                               param_itemsize=4, peak=peak)
    whole = [(f"jit_decode/moe_gmm.{i}", 1.0) for i in range(4)]
    full = reader.read(ctx(whole))
    assert 0 < full < 100
    assert reader.read(ctx(whole[:3])) is None
    assert reader.read(ctx([])) is None
    assert reader.read(ctx(whole[:2] + whole)) is None
