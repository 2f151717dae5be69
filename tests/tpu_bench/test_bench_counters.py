"""The FLOP and byte counters against counts made by hand."""
import pytest

from tpu_bench import counters

M = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 4,
     "num_key_value_heads": 2, "head_dim": 2, "num_hidden_layers": 3,
     "vocab_size": 10}
# per layer: wq 8x8, wk 8x4, wv 8x4, wo 8x8 = 192; mlp 3*8*16 = 384
LAYER = 192 + 384


def test_sizes_and_weight_bytes():
    assert counters.dense_sizes(M)["layer_params"] == LAYER
    assert counters.weight_bytes(M) == (3 * LAYER + 8 * 10) * 2


def test_prefill_flops_by_hand():
    S = 5
    # matmuls 2·S·params per layer; causal QK and PV over 15 pairs,
    # 2·2 flops per pair per head-dim element, 4 heads × 2 dims
    per_layer = 2 * S * LAYER + 4 * 15 * 4 * 2
    assert counters.prefill_flops(M, S) == 3 * per_layer + 2 * 8 * 10


def test_decode_flops_and_bytes_by_hand():
    ctx = [3, 7]
    per_tok = 3 * 2 * LAYER + 2 * 8 * 10
    attn = sum(3 * 4 * c * 4 * 2 for c in ctx)
    assert counters.decode_flops(M, ctx) == 2 * per_tok + attn
    kv_tok = 3 * 2 * 2 * 2 * 2          # layers·(k,v)·KH·hd·bf16
    assert counters.decode_bytes(M, ctx) == \
        counters.weight_bytes(M) + 10 * kv_tok + 2 * kv_tok


def test_prefill_bytes_by_hand():
    S = 4
    kv = 3 * 2 * S * 2 * 2 * 2
    assert counters.prefill_bytes(M, S) == \
        counters.weight_bytes(M) + S * 8 * 2 + kv


def test_roofline_takes_the_larger_bound():
    peaks = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert counters.roofline_s(200.0, 10.0, peaks) == 2.0
    assert counters.roofline_s(100.0, 50.0, peaks) == 5.0


@pytest.mark.parametrize("kind,expect", [
    ("dense", 3 * 28 + 3 * 5 * 6), ("jitter", 3 * 12 + 3 * 5 * 4),
    ("poisson", 3 * 8 + 3 * 5 * 8)])
def test_fleet_kernel_bytes_by_hand(kind, expect):
    assert counters.fleet_kernel_bytes(
        {"kind": kind, "rows": 3, "cols": 5}) == expect
