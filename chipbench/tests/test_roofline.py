"""Operation and byte counts of the kernels, checked by hand."""
import pytest

from chipbench import peaks as P
from chipbench.roofline import bloom_probe, dense_layer

QWEN3_1_7B = {"hidden": 2048, "heads": 16, "kv_heads": 8, "head_dim": 128,
              "ffn": 6144, "weight_bytes": 2, "kv_bytes": 4, "act_bytes": 4}


@pytest.mark.parametrize("pairs,k,nbytes", [
    (64, 7, 64 * (16 + 28 + 4)),        # 3,072 B
    (1000, 3, 1000 * (16 + 12 + 4)),    # 32,000 B
])
def test_bloom_probe_bytes_by_hand(pairs, k, nbytes):
    w = bloom_probe.work(pairs, k)
    assert w["bytes"] == nbytes and w["flops"] == 0
    assert w["int_ops"] == pairs * k * 8
    peaks = P.peaks_for("TPU v5 lite")
    assert bloom_probe.ideal_seconds(pairs, k, peaks) == nbytes / 819e9


def test_dense_layer_decode_by_hand():
    # one new token over 100 resident: projections 2*(2048*2048*2 +
    # 2048*1024*2) = 25,165,824; MLP 2*3*2048*6144 = 75,497,472;
    # attention 4*16*128*101 = 827,392
    w = dense_layer.work(1, 100, QWEN3_1_7B)
    assert w["flops"] == 25_165_824 + 75_497_472 + 827_392
    params = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 6144 + 4096 + 256
    kv_token = 2 * 8 * 128 * 4
    assert dense_layer.layer_params(QWEN3_1_7B) == params == 50_336_000
    assert w["bytes"] == 2 * params + 100 * kv_token + kv_token + 2 * 2048 * 4
    peaks = P.peaks_for("TPU v5 lite")
    # decode is bound by its bytes: ~100 MB at 819 GB/s
    assert dense_layer.ideal_seconds(1, 100, QWEN3_1_7B, peaks) == \
        w["bytes"] / 819e9


def test_dense_layer_prefill_by_hand():
    # 4 new tokens, nothing resident: causal keys 1+2+3+4 = 10
    d = dict(QWEN3_1_7B, hidden=8, heads=2, kv_heads=1, head_dim=4, ffn=16)
    w = dense_layer.work(4, 0, d)
    matmul = 2 * 4 * (8 * 8 * 2 + 8 * 4 * 2 + 3 * 8 * 16)
    assert w["flops"] == matmul + 4 * 2 * 4 * 10
    params = 8 * 8 * 2 + 8 * 4 * 2 + 3 * 8 * 16 + 16 + 8
    assert w["bytes"] == 2 * params + 4 * (2 * 1 * 4 * 4) + 2 * 4 * 8 * 4
    assert dense_layer.head_flops(d, 100) == 2 * 8 * 100
