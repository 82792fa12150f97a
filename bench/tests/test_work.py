"""Work counts from live lengths, by hand at small shapes, and the peaks
table."""
import pytest

from bench.harness import work


def test_decode_attention_counts():
    # 2 heads, 1 KV head, head_dim 4; tokens attending 3 and 5 keys
    f, b = work.decode_attention(2, 1, 4, [3, 5])
    assert f == 4 * 2 * 4 * 3 + 4 * 2 * 4 * 5
    # K and V of the context + q and out, bf16
    assert b == 2 * ((2 * 1 * 4 * 3 + 2 * 2 * 4) + (2 * 1 * 4 * 5 + 2 * 2 * 4))


def test_prefill_attention_counts_causal_keys():
    # a chunk of 3 tokens after 2 cached ones attends 3 + 4 + 5 keys
    f, b = work.prefill_attention(2, 1, 4, [(2, 5)])
    assert f == 4 * 2 * 4 * (3 + 4 + 5)
    assert b == 2 * (2 * 1 * 4 * 2 + (2 * 2 + 2 * 1) * 4 * 3)
    assert work.prefill_attention(2, 1, 4, [(4, 4)]) == (0.0, 0.0)


def test_layer_matmul_params():
    # d=8, 2 heads x 4, 1 KV head x 4, d_ff 16
    assert work.layer_matmul_params(8, 2, 1, 4, 16) \
        == 8 * (2 + 2) * 4 + 2 * 4 * 8 + 3 * 8 * 16


def test_model_flops():
    n = work.layer_matmul_params(8, 2, 1, 4, 16)
    got = work.model_flops(layers=3, d_model=8, heads=2, kv_heads=1,
                           head_dim=4, d_ff=16, vocab=10,
                           prefill_spans=[(0, 2)], decode_contexts=[3],
                           produced=2)
    attn = 4 * 2 * 4 * (1 + 2) + 4 * 2 * 4 * 3
    assert got == 3 * (2 * n * 3 + attn) + 2 * 8 * 10 * 2


def test_least_time_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000.0, 20.0, peak) == 10.0
    assert work.least_time(100.0, 50.0, peak) == 5.0


def test_peaks_of_a_v5e():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(work.UnknownDevice):
        work.peaks("cpu")
