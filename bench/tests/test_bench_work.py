"""The operation and byte counts, against counts made by hand at tiny
shapes."""
import pytest

from yard import work

D = work.LMDims(vocab=10, d_model=4, n_heads=2, n_kv_heads=1, head_dim=2,
                n_layers=3, d_ff=8, n_ffn_mats=2, itemsize=4)


def test_layer_matmuls_by_hand():
    # q 4x4, k 4x2, v 4x2, o 4x4, up 4x8, down 8x4: 16+8+8+16+32+32 = 112
    assert work.layer_matmul_flops(D) == 2 * 112
    assert work.head_flops(D) == 2 * 4 * 10


def test_decode_token_by_hand():
    # attention over 5 positions: 2 heads x 2 dims x 5 x (qk + pv) x 2
    attn = 2 * 2 * 5 * 2 * 2
    assert work.decode_token_flops(D, 5) == 3 * (224 + attn) + 80


def test_prefill_by_hand():
    # causal pairs for 3 tokens: 1 + 2 + 3 = 6
    attn = 6 * 2 * 2 * 2 * 2
    assert work.prefill_flops(D, 3) == 3 * (3 * 224 + attn) + 80


def test_attention_bytes_by_hand():
    dec = work.decode_attention_work(D, 5)
    # keys and values: 5 positions x 1 kv head x 2 dims x 2; q and out 4
    assert dec == {"flops": 80, "bytes": 4 * (20 + 8)}
    fl = work.flash_attention_work(D, 3)
    # 6 causal pairs x 2 heads x 2 dims x (qk + pv) x 2
    assert fl == {"flops": 96, "bytes": 4 * 3 * (8 + 4)}


def test_roofline_takes_the_slower_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_seconds({"flops": 50, "bytes": 1}, peak) == 0.5
    assert work.roofline_seconds({"flops": 1, "bytes": 50}, peak) == 5.0


def test_config_dims_match_the_published_widths():
    from yard import common
    cell = common.load_cell("serve-qwen3-1.7b-poisson")
    d = work.LMDims.from_config(cell.config)
    assert (d.d_model, d.n_heads, d.n_kv_heads, d.head_dim, d.n_layers,
            d.d_ff, d.vocab) == (2048, 16, 8, 128, 28, 6144, 151936)
    # 2.74 GFLOP a decoded token before attention
    assert work.decode_token_flops(d, 0) == pytest.approx(2.74e9, rel=0.01)
    # the ungated FFN's two matrices, float32 cache elements
    assert (d.n_ffn_mats, d.itemsize) == (2, 4)
    gated = dict(cell.config, departures={}, torch_dtype="bfloat16")
    assert (work.LMDims.from_config(gated).n_ffn_mats,
            work.LMDims.from_config(gated).itemsize) == (3, 2)
