"""Work counts against hand arithmetic for one layer of each
configuration, and bytes that follow the live arrays' dtypes."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from servebench import spec, work  # noqa: E402


def one_layer(name):
    return dict(spec.config(name), num_hidden_layers=1)


def test_granite_layer_by_hand():
    c = one_layer("granite_3_8b")
    # q 4096x4096, k and v 4096x1024, o 4096x4096, SwiGLU 3 x 4096x12800
    mm = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 12800
    assert work.layer_matmul_params(c) == mm == 199_229_440
    unembed = 2 * 4096 * 49155
    # 10 tokens: matmuls on each, 55 causal pairs x 4 x 32 heads x 128
    assert work.prefill_flops(c, 10) == (2 * mm * 10 + 4 * 32 * 128 * 55
                                         + unembed)
    # two live slots attending to 5 and 7 positions
    assert work.decode_flops(c, [5, 7]) == (2 * (2 * mm + unembed)
                                            + 4 * 32 * 128 * 12)
    # f32: the layer's weights and two RMSNorm weights, the unembedding,
    # the final norm, two embedding rows; KV: 2 x 8 heads x 128 x 4 bytes
    # per position
    weights = mm + 2 * 4096 + 4096 * 49155 + 4096 + 2 * 4096
    assert work.decode_bytes(c, [5, 7], 4, 4) == (weights * 4
                                                  + 2 * 8 * 128 * 4 * 12)


def test_starcoder2_layer_by_hand():
    c = one_layer("starcoder2_15b")
    # q 6144x6144, k and v 6144x512, o 6144x6144, GeLU 2 x 6144x24576
    mm = 6144 * 6144 * 2 + 6144 * 512 * 2 + 2 * 6144 * 24576
    assert work.layer_matmul_params(c) == mm
    # two LayerNorms with bias, q/k/v biases
    assert work.layer_other_params(c) == 4 * 6144 + 6144 + 2 * 512
    unembed = 2 * 6144 * 49152
    assert work.prefill_flops(c, 3) == (2 * mm * 3 + 4 * 48 * 128 * 6
                                        + unembed)
    weights = mm + 4 * 6144 + 6144 + 1024 + 6144 * 49152 + 2 * 6144 + 6144
    assert work.decode_bytes(c, [9], 4, 4) == (weights * 4
                                               + 2 * 4 * 128 * 4 * 9)


@pytest.mark.parametrize("name", ["granite_3_8b", "starcoder2_15b"])
def test_bf16_halves_the_bytes(name):
    c = spec.config(name)
    keys = [300, 17, 1000]
    assert work.decode_bytes(c, keys, 2, 2) * 2 == work.decode_bytes(
        c, keys, 4, 4)


def test_least_time_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000, 50, peak) == (10.0, "compute")
    assert work.least_time(100, 50, peak) == (5.0, "memory")


def test_decode_step_is_memory_bound_on_v5e():
    c = spec.config("granite_3_8b")
    peak = spec.peak("TPU v5 lite")
    keys = [600] * 8
    t, bound = work.least_time(work.decode_flops(c, keys),
                               work.decode_bytes(c, keys, 4, 4), peak)
    assert bound == "memory"
    # 7.99 GB of f32 parameters, less the embedding table, at 819 GB/s
    assert 0.0085 < t < 0.0105
