"""Small pure-python units: caps sizing, registry, shapes."""

import math

from repro.core.device_dbscan import GritCaps
from repro.configs import canonical, list_archs, get_shape, SHAPES
from repro.configs.registry import long_500k_supported


def test_gritcaps_for_dim_fanout_bound():
    for d in (2, 3, 5, 7):
        caps = GritCaps.for_dim(d)
        r = 2 * math.ceil(math.sqrt(d)) + 1
        assert caps.frontier_cap == max(min(r ** (d - 1), 256), 8)
        assert caps.merge_iters == 16


def test_registry_canonical_ids():
    assert canonical("qwen2-1.5b") == "qwen2_1_5b"
    assert canonical("qwen1.5-0.5b") == "qwen1_5_0_5b"
    assert canonical("mixtral-8x7b") == "mixtral_8x7b"
    assert len(list_archs()) == 10


def test_shape_registry():
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                           "long_500k"}
    assert get_shape("train_4k").kind == "train"
    assert get_shape("decode_32k").kind == "decode"
    assert get_shape("long_500k").global_batch == 1


def test_long_500k_policy():
    assert long_500k_supported("rwkv6-3b")
    assert long_500k_supported("zamba2-2.7b")
    assert long_500k_supported("mixtral-8x7b")     # bounded SWA window
    assert not long_500k_supported("gemma2-27b")   # global layers

