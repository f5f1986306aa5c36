"""The grouped expert FFN kernel (interpret mode on the CPU) against its
bf16 oracle, over rows per expert of 0, 1, 7 and 64 and ragged offsets.

Only rows inside a group are defined; the kernel must equal the
default-precision grouped matmuls there (every operand rounded to
bfloat16, float32 accumulation), up to the summation order inside a tile
and the rounding of the hidden activation to bfloat16, which the two
compute from sums in another order."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe_gmm.kernel import moe_gmm_pallas, work_items
from repro.kernels.moe_gmm.ops import moe_gmm
from repro.kernels.moe_gmm.ref import moe_gmm_ref

# of max |y|: kernel and oracle both round the hidden activation to bf16,
# from f32 sums taken in another order, so an element that straddles a
# rounding boundary moves by one bf16 ulp (2^-8 of itself); 1.4e-4 measured
TOL = 1e-3

# rows per held expert, and rows past the last group (pairs routed to
# experts held elsewhere)
SIZES = {
    "empty": ([0, 0, 0, 0], 8),
    "one_each": ([1, 1, 1, 1], 4),
    "seven": ([7, 0, 7, 7], 11),
    "sixty_four": ([64, 64, 0, 64], 0),
    "ragged": ([0, 1, 7, 64, 3, 0, 40, 9], 30),
}


def _weights(key, E, K, F):
    kg, ki, ko = jax.random.split(key, 3)
    return (jax.random.normal(kg, (E, K, F)) / K ** 0.5,
            jax.random.normal(ki, (E, K, F)) / K ** 0.5,
            jax.random.normal(ko, (E, F, K)) / F ** 0.5)


@pytest.mark.parametrize("case", sorted(SIZES))
@pytest.mark.parametrize("K,F", [(256, 384), (384, 1024)])
def test_kernel_matches_bf16_grouped_ffn(case, K, F):
    sizes, rest = SIZES[case]
    E, M = len(sizes), sum(sizes) + rest
    offsets = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)]), jnp.int32)
    kx, kw = jax.random.split(jax.random.key(M * 1000 + K + F))
    x = jax.random.normal(kx, (M, K), jnp.float32)
    wg, wi, wo = _weights(kw, E, K, F)
    ref = moe_gmm_ref(x, wg, wi, wo, offsets)
    held = int(offsets[-1])
    scale = max(float(jnp.max(jnp.abs(ref))), 1.0)

    def close(y, want):
        assert y.shape == (M, K) and y.dtype == jnp.float32
        err = float(jnp.max(jnp.abs(y[:held] - want[:held]), initial=0.0))
        assert err <= TOL * scale, err

    # the op: one tile for up to 256 rows, the layer of a stack
    close(moe_gmm(x, wg, wi, wo, offsets, interpret=True), ref)
    stacks = [jnp.stack([-w, w]) for w in (wg, wi, wo)]
    close(moe_gmm(x, *stacks, offsets, 1, interpret=True), ref)
    # small tiles: several row tiles, experts spanning tiles and sharing
    # them, several hidden tiles, padding items
    tm = 8
    xp = jnp.pad(x, ((0, -M % tm), (0, 0)))
    y = moe_gmm_pallas(xp, *(w[None] for w in (wg, wi, wo)), offsets, 0,
                       tm=tm, tf=128, interpret=True)
    close(y[:M], ref)


def test_work_items_read_each_expert_once_in_one_tile():
    """A decode step's rows lie in one tile: one item per expert, each the
    tile's, so each expert's weights are read once; the first item zeroes
    the tile and no item is padding."""
    offsets = jnp.asarray([0, 3, 3, 10, 12], jnp.int32)
    expert, tile, first, valid = work_items(offsets, 128, 1)
    assert expert.tolist() == [0, 1, 2, 3]
    assert tile.tolist() == [0, 0, 0, 0]
    assert first.tolist() == [1, 0, 0, 0]
    assert valid.tolist() == [1, 1, 1, 1]


def test_work_items_bound_and_padding():
    """Experts spanning tiles: items in tile order, padding repeats the last
    real item and is marked so."""
    offsets = jnp.asarray([0, 10, 10, 20], jnp.int32)
    expert, tile, first, valid = work_items(offsets, 8, 3)
    # expert 0: tiles 0, 1; expert 1 (empty): tile 1; expert 2: tiles 1, 2
    assert expert.tolist() == [0, 0, 1, 2, 2]
    assert tile.tolist() == [0, 1, 1, 1, 2]
    assert first.tolist() == [1, 1, 0, 0, 1]
    assert valid.tolist() == [1, 1, 1, 1, 1]
    expert, tile, first, valid = work_items(
        jnp.asarray([0, 2, 4, 6], jnp.int32), 8, 3)
    assert expert.tolist() == [0, 1, 2, 2, 2]
    assert tile.tolist() == [0, 0, 0, 0, 0]
    assert first.tolist() == [1, 0, 0, 0, 0]
    assert valid.tolist() == [1, 1, 1, 0, 0]


def test_gradient_is_the_plain_grouped_ffn():
    kx, kw, kg = jax.random.split(jax.random.key(3), 3)
    offsets = jnp.asarray([0, 5, 5, 12], jnp.int32)
    x = jax.random.normal(kx, (16, 128), jnp.float32)
    ws = _weights(kw, 3, 128, 256)
    g = jax.random.normal(kg, (16, 128), jnp.float32)
    got = jax.vjp(lambda x, *w: moe_gmm(x, *w, offsets, interpret=True),
                  x, *ws)[1](g)

    def plain(x, wg, wi, wo):
        mm = lambda a, w: jax.lax.ragged_dot(a, w, jnp.diff(offsets))  # noqa
        return mm(jax.nn.silu(mm(x, wg)) * mm(x, wi), wo)
    want = jax.vjp(plain, x, *ws)[1](g)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * float(
            jnp.max(jnp.abs(b)))


def test_layer_gradient_through_kernel_ignores_rows_held_elsewhere(
        monkeypatch):
    """The MoE layer on the kernel's path (interpret mode in
    ``expert_ffn``'s place), holding 2 of 4 experts: the kernel leaves the
    rows of pairs routed elsewhere undefined, here NaN, the worst they can
    hold. The layer's output and its gradients (router, selection bias,
    experts, input) stay finite and match the plain path's."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import moe_layer

    cfg = dataclasses.replace(get_config("lfm2_24b_a2b").reduced(),
                              experts_held=(0, 2))
    p = moe_layer.init_moe(jax.random.key(7), cfg, jnp.float32)
    p["bias"] = 0.1 * jax.random.normal(jax.random.key(8), p["bias"].shape)
    x = jax.random.normal(jax.random.key(9), (2, 8, cfg.d_model))
    probe = jax.random.normal(jax.random.key(10), x.shape)

    def loss(p, x):
        out, _ = moe_layer.moe_forward(p, x, cfg)
        return jnp.sum(out * probe)
    want = jax.grad(loss, argnums=(0, 1))(p, x)

    def kernel_ffn(x, wg, wi, wo, offsets):
        y = moe_gmm(x, wg, wi, wo, offsets, interpret=True)
        rows = jnp.arange(x.shape[0])[:, None]
        return jnp.where(rows < offsets[-1], y, jnp.nan)
    monkeypatch.setattr(moe_layer, "expert_ffn", kernel_ffn)
    out, _ = moe_layer.moe_forward(p, x, cfg)
    assert bool(jnp.isfinite(out).all())
    got = jax.grad(loss, argnums=(0, 1))(p, x)
    # the kernel's forward rounds its operands to bf16 (2^-8 relative) and
    # the gradients read its outputs; the plain path here is float32
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.isfinite(a).all())
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-2 * float(
            jnp.max(jnp.abs(b)))


EXPERT_PARALLEL = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import moe_layer
from repro.models.sharding import standard_rules, use_rules

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))
rules = standard_rules(False)
for arch in ("phi3_5_moe", "lfm2_24b_a2b"):
    cfg = get_config(arch).reduced()
    p = moe_layer.init_moe(jax.random.key(1), cfg, jnp.float32)
    if "bias" in p:
        p["bias"] = 0.1 * jax.random.normal(jax.random.key(2), p["bias"].shape)
    x = jax.random.normal(jax.random.key(3), (4, 6, cfg.d_model))
    probe = jax.random.normal(jax.random.key(4), x.shape)

    def loss(p, x, sharded):
        if not sharded:
            return jnp.sum(moe_layer.moe_forward(p, x, cfg)[0] * probe)
        with use_rules(rules, mesh):
            assert moe_layer._expert_axis(cfg, x.shape[0]) is not None
            return jnp.sum(moe_layer.moe_forward(p, x, cfg)[0] * probe)
    want = jax.value_and_grad(loss, argnums=(0, 1))(p, x, False)
    ps = {k: jax.device_put(v, NamedSharding(
              mesh, P("model", None, None) if k in ("wi", "wg", "wo")
              else P())) for k, v in p.items()}
    with mesh:
        text = jax.jit(lambda p, x: loss(p, x, True)).lower(ps, x).compile(
            ).as_text()
        got = jax.jit(jax.value_and_grad(
            lambda p, x: loss(p, x, True), argnums=(0, 1)))(ps, x)
    assert "all-reduce" in text
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        err = float(jnp.max(jnp.abs(a - b)))
        assert err <= 1e-5 * float(jnp.max(jnp.abs(b))), (arch, err)
print("EXPERT_PARALLEL_OK")
"""


def test_expert_parallel_layer_matches_the_whole_layer():
    """Under sharding rules that put the 4 experts of the reduced
    phi3_5_moe (softmax router) and lfm2 (sigmoid router with a selection
    bias) on a 4-way model axis, each shard computes its own expert's pairs
    and one all-reduce joins them: the loss and every gradient equal the
    unsharded layer's, up to float32 sums in another order. Runs in a
    subprocess on 8 host devices."""
    import subprocess
    import sys
    r = subprocess.run([sys.executable, "-c", EXPERT_PARALLEL],
                       capture_output=True, text=True,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert "EXPERT_PARALLEL_OK" in r.stdout, r.stdout + r.stderr
