"""Operation and byte counts against hand-worked values, and the peaks
table."""
import pytest

import counts
import peaks

TINY = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
        "head_dim": 4, "vocab_size": 10,
        "ffn": {"kind": "dense", "d_ff": 16}}


def test_executed_tiles_follow_the_skip_rule():
    # page 128, 8 tiles per row, q tile 64, width 3:
    # len 0 -> 1 tile, len 100 -> ceil(103/128) = 1, len 300 -> 3,
    # len 1020 -> min(8, ceil(1023/128) = 8) = 8
    assert counts.executed_kv_tiles(3, [0, 100, 300, 1020], 128, 8) == 13
    # width 70 spans two query tiles: tile 0 sees len+64, tile 1 len+70
    assert counts.executed_kv_tiles(70, [100], 128, 8) == 2 + 2


def test_paged_attention_work():
    w = counts.paged_attention(3, [0, 100, 300], page=128, n_kv_tiles=8,
                               n_heads=2, n_kv_heads=2, head_dim=16)
    # 2 kv heads x 5 tiles = 10 tiles; g = 1, rows = 64
    assert w.flops == 10 * 4 * 64 * 128 * 16
    # each tile: one K and one V page of 128 x 16 bf16
    kv = 10 * 2 * 128 * 16 * 2
    # q and o: 3 rows x 2 kv heads x 1 query tile, 64 x 16 bf16 each way
    qo = 6 * 2 * 64 * 16 * 2
    assert w.bytes == kv + qo


def test_gqa_groups_share_kv_pages():
    mha = counts.paged_attention(1, [500], 128, 8, 4, 4, 16)
    gqa = counts.paged_attention(1, [500], 128, 8, 4, 1, 16)
    assert gqa.flops == mha.flops          # same queries, same keys
    assert gqa.bytes < mha.bytes           # a quarter of the pages


def test_roofline_seconds_take_the_larger_bound():
    w = counts.Work(flops=197e12, bytes=819e9 * 2)
    assert w.seconds(197e12, 819e9) == pytest.approx(2.0)


def test_model_flops():
    # per layer: q,o 8*2*4*2 = 128, k,v 8*1*4*2 = 64, ffn 3*8*16 = 384;
    # 2 layers = 1152, head 8*10 = 80 -> 1232 weights
    assert counts.matmul_params_per_token(TINY) == 1232
    # two tokens at contexts 5 and 7: 2*1232*2 + 4*2*2*4*12
    assert counts.model_flops(TINY, [5, 7]) == 2 * 1232 * 2 + 4 * 2 * 2 * 4 * 12


def test_moe_counts_active_experts_and_router():
    moe = dict(TINY, ffn={"kind": "moe", "d_ff": 16, "n_experts": 8,
                          "top_k": 2})
    # ffn: 2 experts * 384 + router 8*8 = 832 per layer
    assert counts.matmul_params_per_token(moe) == 2 * (192 + 832) + 80


def test_peaks_table():
    p = peaks.for_kind("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_per_s) == (197e12, 819e9)
    with pytest.raises(ValueError):
        peaks.for_kind("TPU v9 imaginary")
