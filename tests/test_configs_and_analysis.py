"""Configs (assigned table fidelity) + HLO analysis utilities."""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import hlo_graph as HG
from repro.configs import ARCH_ALIASES, ARCH_IDS, get_config

EXPECTED = {
    # arch: (layers, d_model, heads, kv, d_ff, vocab)
    "qwen1_5_4b": (40, 2560, 20, 20, 6912, 151936),
    "granite_3_8b": (40, 4096, 32, 8, 12800, 49155),
    "llama3_405b": (126, 16384, 128, 8, 53248, 128256),
    "starcoder2_15b": (40, 6144, 48, 4, 24576, 49152),
    "llama4_maverick": (48, 5120, 40, 8, 8192, 202048),
    "whisper_large_v3": (32, 1280, 20, 20, 5120, 51866),
    "xlstm_350m": (24, 1024, 4, 4, 0, 50304),
    "recurrentgemma_2b": (26, 2560, 10, 1, 7680, 256000),
    "phi3_5_moe": (32, 4096, 32, 8, 6400, 32064),
    "chameleon_34b": (48, 8192, 64, 8, 22016, 65536),
    "lfm2_24b_a2b": (40, 2048, 32, 8, 1536, 65536),
}

PARAM_RANGES = {
    "qwen1_5_4b": (3.5e9, 4.5e9),
    "granite_3_8b": (7.5e9, 9e9),
    "llama3_405b": (3.9e11, 4.2e11),
    "starcoder2_15b": (1.45e10, 1.7e10),
    "llama4_maverick": (3.8e11, 4.2e11),
    "phi3_5_moe": (4.0e10, 4.4e10),
    "chameleon_34b": (3.2e10, 3.6e10),
    "lfm2_24b_a2b": (2.3e10, 2.5e10),
}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_assigned_config_exact(arch):
    c = get_config(arch)
    L, D, H, K, F, V = EXPECTED[arch]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (L, D, H, K, F, V)
    assert c.source, "every config must cite its source"


@pytest.mark.parametrize("arch,rng_", list(PARAM_RANGES.items()))
def test_param_counts_plausible(arch, rng_):
    lo, hi = rng_
    assert lo <= get_config(arch).param_count() <= hi


def test_moe_active_params():
    c = get_config("llama4_maverick")
    assert 1.6e10 <= c.active_param_count() <= 1.8e10     # "A17B"
    c = get_config("phi3_5_moe")
    assert 6.0e9 <= c.active_param_count() <= 7.2e9       # "A6.6B"


def test_aliases_resolve():
    for alias in ARCH_ALIASES:
        assert get_config(alias) is not None


def test_reduced_configs_small():
    for arch in ARCH_IDS:
        r = get_config(arch).reduced()
        assert r.n_layers <= 4 and r.d_model <= 512
        if r.n_experts:
            assert r.n_experts <= 4


# --- HLO graph analysis -------------------------------------------------------

def test_trip_count_multiplication():
    D, G = 64, 7

    def f(w, x):
        def body(x, wi):
            return jnp.tanh(x @ wi), None
        x, _ = jax.lax.scan(body, x, w)
        return x.sum()

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((G, D, D), jnp.float32),
        jax.ShapeDtypeStruct((4, D), jnp.float32)).compile()
    mc = HG.analyze(comp.as_text())
    assert mc.dot_flops == pytest.approx(2 * 4 * D * D * G, rel=0.01)
    assert mc.loops and mc.loops[0][1] == G


_INLINE_SHAPE_HLO = """
HloModule m

ENTRY %main (p0: f32[4,64], p1: f32[64,96]) -> f32[4,96] {
  %p0 = f32[4,64]{1,0} parameter(0)
  %p1 = f32[64,96]{1,0} parameter(1)
  ROOT %dot.1 = f32[4,96]{1,0} dot(f32[4,64]{1,0:T(8,128)} %p0, f32[64,96]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


def test_dot_parsing_with_inline_operand_shapes():
    """Regression: the operand regex used to match the dtype token (``f32``)
    of inline operand shapes, so contraction size collapsed to 1 and dot
    FLOPs were undercounted by the full contraction dimension. The inline
    operand shape (here with a TPU tiled layout, which nests parens) must be
    read directly."""
    mc = HG.analyze(_INLINE_SHAPE_HLO)
    assert mc.dot_flops == pytest.approx(2 * 4 * 96 * 64)


def test_dot_parsing_falls_back_to_defining_op():
    txt = """
HloModule m

ENTRY %main (p0: f32[8,32], p1: f32[32,16]) -> f32[8,16] {
  %p0 = f32[8,32]{1,0} parameter(0)
  %p1 = f32[32,16]{1,0} parameter(1)
  ROOT %dot.2 = f32[8,16]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    mc = HG.analyze(txt)
    assert mc.dot_flops == pytest.approx(2 * 8 * 16 * 32)


def test_async_collective_suffix_stripped_not_rstripped():
    """``rstrip("-start")`` strips a character *set*; the opcode must lose
    only a literal ``-start``/``-done`` suffix, and ``-done`` halves of async
    pairs must not be double-counted."""
    assert HG._strip_async_suffix("all-reduce-start") == "all-reduce"
    assert HG._strip_async_suffix("all-reduce-done") == "all-reduce"
    assert HG._strip_async_suffix("reduce-scatter") == "reduce-scatter"
    assert HG._strip_async_suffix("all-to-all") == "all-to-all"
    txt = """
HloModule m

ENTRY %main (p0: f32[128]) -> f32[128] {
  %p0 = f32[128]{0} parameter(0)
  %ar-start = f32[128]{0} all-reduce-start(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %ar-done = f32[128]{0} all-reduce-done(%ar-start)
}
"""
    mc = HG.analyze(txt)
    assert mc.coll_counts.get("all-reduce") == 1


def test_wire_factors():
    assert HG._wire_factor("all-reduce", 16) == pytest.approx(2 * 15 / 16)
    assert HG._wire_factor("all-gather", 16) == pytest.approx(15 / 16)
    assert HG._wire_factor("reduce-scatter", 16) == 15.0
    assert HG._wire_factor("collective-permute", 16) == 1.0
