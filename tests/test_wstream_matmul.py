"""The weight-streaming matmul kernel (interpret mode on the CPU) and the
``layers.proj`` helper that puts it on the model path.

The kernel must equal the default-precision matmul it replaces: both
operands rounded to bfloat16, products accumulated in float32. Only the
summation order inside a tile may differ. On the CPU ``proj`` must be
exactly ``x @ w``, which keeps every family's CPU results bit-identical.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.wstream_matmul.kernel import wstream_matmul_pallas
from repro.kernels.wstream_matmul.ops import wstream_matmul
from repro.kernels.wstream_matmul.ref import wstream_matmul_ref
from repro.models import layers as L

TOL = 1e-5          # of max |y|


def _close(y, ref):
    assert y.shape == ref.shape and y.dtype == jnp.float32
    err = float(jnp.max(jnp.abs(y - ref)))
    assert err <= TOL * float(jnp.max(jnp.abs(ref))), err


@pytest.mark.parametrize("M", [1, 8, 64, 200])
@pytest.mark.parametrize("K,N", [(256, 384), (512, 1000), (384, 128)])
def test_kernel_matches_bf16_dot(M, K, N):
    kx, kw = jax.random.split(jax.random.key(M * 1000 + K + N))
    x = jax.random.normal(kx, (M, K), jnp.float32)
    w = jax.random.normal(kw, (K, N), jnp.float32)
    ref = wstream_matmul_ref(x, w)
    # the op: default tiles, and the layout the TPU stores w in
    _close(wstream_matmul(x, w, interpret=True), ref)
    # one layer of a stack, small tiles: several N and K tiles, a ragged
    # last N tile where 256 does not divide N, ragged M tiles where 64
    # does not divide M
    stack = jnp.stack([-w, w, 2 * w])
    small = dict(tm=64, tk=128, tn=256, interpret=True)
    _close(wstream_matmul_pallas(x, stack, 2, **small), 2 * ref)
    _close(wstream_matmul_pallas(x, jnp.swapaxes(stack, 1, 2), 1,
                                 transposed=True, **small), ref)


def test_gradient_is_the_plain_matmuls():
    kx, kw, kg = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(kx, (2, 3, 256), jnp.float32)
    w = jax.random.normal(kw, (256, 384), jnp.float32)
    g = jax.random.normal(kg, (2, 3, 384), jnp.float32)

    def vjp(f):
        return jax.vjp(f, x, w)[1](g)

    got = vjp(lambda x, w: wstream_matmul(x, w, interpret=True))
    want = vjp(lambda x, w: x @ w)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * float(
            jnp.max(jnp.abs(b)))


def test_proj_is_exactly_matmul_on_cpu():
    kx, kw = jax.random.split(jax.random.key(5))
    x = jax.random.normal(kx, (2, 3, 256), jnp.float32)
    stack = jax.random.normal(kw, (4, 256, 384), jnp.float32)
    w = stack[1]
    assert (L.proj(x, w) == x @ w).all()
    assert (jax.jit(L.proj)(x, w) == x @ w).all()
    # a layer of a stack, read in place, is the same as its slice
    view = jax.jit(lambda x, s, i: L.proj(x, L.LayerWeight(s, i)))
    assert (view(x, stack, 1) == x @ w).all()
    # other dtypes keep the plain matmul
    wb = w.astype(jnp.bfloat16)
    assert (L.proj(x, wb) == x @ wb).all()


def test_layer_params_views_only_dense_projections():
    stacked = {
        "ln1": {"w": jnp.ones((4, 8))},
        "attn": {"wq": jnp.ones((4, 8, 16)), "bq": jnp.zeros((4, 16)),
                 "q_norm": jnp.ones((4, 2))},
        "ffn": {"wi": jnp.ones((4, 2, 8, 16)), "router": jnp.ones((4, 8, 2))},
    }
    p, = L.layer_params((stacked,), 2)
    assert isinstance(p["attn"]["wq"], L.LayerWeight)
    assert p["attn"]["wq"].shape == (8, 16)
    assert p["attn"]["bq"].shape == (16,)
    assert p["ln1"]["w"].shape == (8,)
    # an expert stack is viewed like a projection, a router is sliced
    assert isinstance(p["ffn"]["wi"], L.LayerWeight)
    assert p["ffn"]["wi"].shape == (2, 8, 16)
    assert p["ffn"]["router"].shape == (8, 2)
