"""The two KV cache layouts of ``layers.kv_cache_shape``: the lane-dense
(B, S, K * hd) and the split (B, S, K, hd). The prefill fill and the decode
update write the same values in either, and decode attention reads the
same result from either, to f32 round-off, at any head_dim."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L


@pytest.mark.parametrize("window", [None, 16])
@pytest.mark.parametrize("per_slot", [False, True])
@pytest.mark.parametrize("hd", [64, 128])
def test_lane_dense_layout_matches_split(hd, per_slot, window):
    B, Hq, K, S, max_len = 3, 8, 2, 20, 32
    Sc = min(max_len, window) if window else max_len
    ks = jax.random.split(jax.random.key(hd + 2 * per_slot), 5)
    q = jax.random.normal(ks[0], (B, 1, Hq, hd), jnp.float32)
    k, v = (jax.random.normal(kk, (B, S, K, hd), jnp.float32)
            for kk in ks[1:3])
    k_new, v_new = (jax.random.normal(kk, (B, 1, K, hd), jnp.float32)
                    for kk in ks[3:5])
    pos = jnp.asarray([S, S - 3, S - 8]) if per_slot else jnp.asarray(S)

    caches = {}
    for layout in ("split", "flat"):
        shape = ((B, Sc, K, hd) if layout == "split" else (B, Sc, K * hd))
        kc, vc = (L.cache_fill_prefill(jnp.zeros(shape), x, window=window)
                  for x in (k, v))
        caches[layout, "prefill"] = (kc, vc)
        kc, vc = (L.cache_update_decode(c, x, pos, window=window)
                  for c, x in ((kc, k_new), (vc, v_new)))
        caches[layout, "decode"] = (kc, vc)
        caches[layout, "out"] = L.decode_attention(q, kc, vc, pos,
                                                   window=window)
    for phase in ("prefill", "decode"):
        for split, flat in zip(caches["split", phase], caches["flat", phase]):
            assert flat.shape == (B, Sc, K * hd)
            np.testing.assert_array_equal(np.asarray(flat),
                                          np.asarray(split).reshape(flat.shape))
    out_split, out_flat = caches["split", "out"], caches["flat", "out"]
    assert out_flat.shape == out_split.shape == (B, 1, Hq, hd)
    np.testing.assert_allclose(np.asarray(out_flat), np.asarray(out_split),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("hd,shape", [(64, (2, 7, 4 * 64)),
                                      (96, (2, 7, 4 * 96)),
                                      (128, (2, 7, 4, 128)),
                                      (256, (2, 7, 4, 256))])
def test_kv_cache_shape_is_lane_dense(hd, shape):
    assert L.kv_cache_shape(2, 7, 4, hd) == shape
