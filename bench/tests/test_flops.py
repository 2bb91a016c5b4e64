"""Operation and byte counts from shapes, against counts made by hand."""

import pytest

from bench import flops

S = {"L": 2, "d": 8, "H": 4, "K": 2, "D": 2, "F": 16, "V": 32}
PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_matmul_params_per_layer():
    # q 8*4*2, k and v 2*8*2*2, o 4*2*8, gated MLP 3*8*16
    assert flops.matmul_params_per_layer(S) == 64 + 64 + 64 + 384


def test_flash_cost_counts_causal_pairs():
    f, b = flops.flash_cost(3, S)
    assert f == 4 * 4 * 2 * 6  # 6 (query, key) pairs at or before each query
    assert b == 2 * 3 * 2 * (2 * 4 + 2 * 2)


def test_paged_cost_counts_held_tokens():
    f, b = flops.paged_cost([5, 1], S)
    assert f == 4 * 4 * 2 * 6
    assert b == 2 * (2 * 2 * 2 * 6 + 2 * 4 * 2 * 2)


def test_model_flops():
    per_layer = 576
    assert flops.prefill_flops(3, S) == 2 * 3 * 2 * per_layer + 2 * 4 * 4 * 2 * 6 + 2 * 8 * 32
    assert flops.decode_flops([5, 1], S) == 2 * 2 * (2 * per_layer + 8 * 32) + 2 * 4 * 4 * 2 * 6


def test_least_time_is_the_larger_bound():
    assert flops.least_time(200.0, 10.0, PEAK) == pytest.approx(2.0)
    assert flops.least_time(100.0, 50.0, PEAK) == pytest.approx(5.0)


def test_sizes_from_a_configuration():
    cfg = {"num_hidden_layers": 12, "hidden_size": 4096, "num_attention_heads": 32,
           "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 11008,
           "vocab_size": 64000}
    s = flops.dense_sizes(cfg)
    # Yi-9B's 12 layers: 2.08 B weights a token multiplies through
    assert s["L"] * flops.matmul_params_per_layer(s) == 12 * 173015040
