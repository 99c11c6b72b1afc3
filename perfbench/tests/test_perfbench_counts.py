"""Operation and byte counts against hand arithmetic at Qwen3 widths."""

import pytest

from perfbench import counts, model


def dims(name):
    return model.Dims.from_config(model.load_config(name))


@pytest.mark.parametrize("name,n_params,kv", [
    # 12 x (4096*(4096+1024+1024) + 4096*4096 + 3*4096*12288
    #       + 2*4096 + 2*128) + 2*151936*4096 + 4096
    ("qwen3-8b-l12", 3_560_020_992, 12 * 2 * 8 * 128 * 2),
    # 4 x (5120*(8192+1024+1024) + 8192*5120 + 3*5120*25600
    #      + 2*5120 + 2*128) + 2*151936*5120 + 5120
    ("qwen3-32b-l4", 3_506_223_104, 4 * 2 * 8 * 128 * 2),
])
def test_params_and_kv_bytes(name, n_params, kv):
    d = dims(name)
    assert counts.params(d) == n_params
    assert counts.kv_bytes_per_token(d) == kv
    assert kv in (48 * 1024, 16 * 1024)


def test_attention_flops_by_hand():
    d = dims("qwen3-8b-l12")
    # queries at positions 2 and 3 see 3 and 4 keys: 7 key visits, each
    # 4 * 32 heads * 128 flops, over 12 layers
    assert counts.attention_flops(d, 2, 4) == 7 * 4 * 32 * 128 * 12
    # one decode query over 10 keys
    assert counts.attention_flops(d, 9, 10) == 10 * 4 * 32 * 128 * 12


def test_bytes_by_hand():
    d = dims("qwen3-32b-l4")
    # prefill of [4, 8): K and V of 8 positions (8 kv heads x 128, bf16),
    # Q and O of 4 positions (64 heads x 128, bf16), 4 layers
    want = (2 * 8 * 8 * 128 * 2 + 2 * 4 * 64 * 128 * 2) * 4
    assert counts.prefill_attention_bytes(d, 4, 8) == want
    assert counts.decode_attention_bytes(d, 8) == \
        (2 * 8 * 8 * 128 * 2 + 2 * 64 * 128 * 2) * 4


def test_forward_flops_by_hand():
    d = dims("qwen3-8b-l12")
    mm = 2 * (4096 * 6144 + 4096 * 4096 + 3 * 4096 * 12288) * 12
    head = 2 * 4096 * 151936
    assert counts.forward_flops(d, 0, 1, 1) == \
        mm + head + counts.attention_flops(d, 0, 1)
    assert counts.forward_flops(d, 0, 3, 0) == \
        3 * mm + counts.attention_flops(d, 0, 3)


def test_peaks_table():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
