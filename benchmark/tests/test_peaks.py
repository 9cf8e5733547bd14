import os

import pytest

import costs

PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json")


def test_known_kind_has_its_peaks():
    p = costs.load_peaks(PEAKS, "TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "_source", ""])
def test_unknown_kind_is_an_error_not_a_default(kind):
    with pytest.raises(KeyError):
        costs.load_peaks(PEAKS, kind)


def test_bytes_from_shapes():
    phi = {"dim": 3072, "ffn_dim": 8192, "n_layers": 32, "n_heads": 32, "n_kv_heads": 32,
           "vocab_size": 32064, "sliding_window": 2047}
    assert costs.kv_bytes_per_token(phi) == 393216
    # 32 x (4 x 3072^2 + 3 x 3072 x 8192) + 3072 x 32064 weights, bf16
    assert costs.weight_stream_bytes(phi) == 2 * (32 * (4 * 3072**2 + 3 * 3072 * 8192) + 3072 * 32064)
    assert costs.decode_step_bytes(phi, [100, 5000]) == (
        costs.weight_stream_bytes(phi) + (100 + 2047) * 393216)
