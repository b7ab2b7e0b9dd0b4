"""``work.py`` against hand counts for one layer of each configuration."""
from __future__ import annotations

import pytest

import work

GRANITE = work.Widths(layers=1, d=4096, heads=32, kv_heads=8, head_dim=128, ff=12800,
                      vocab=49155)
MOE = work.Widths(layers=1, d=1024, heads=16, kv_heads=8, head_dim=64, ff=512, vocab=49155,
                  experts=32, top_k=8)
# Weights one token multiplies in one layer, counted by hand.
GRANITE_LAYER = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 12800  # 199,229,440
MOE_ATTN = 1024 * 1024 * 2 + 1024 * 512 * 2  # 3,145,728
MOE_EXPERT = 3 * 1024 * 512  # 1,572,864


def test_granite_layer_weights():
    assert sum(k * n for k, n in GRANITE.projections()) == GRANITE_LAYER == 199_229_440


def test_granite_decode_step():
    s = work.decode_step(GRANITE, [100])
    logits = 2 * 4096 * 49155
    assert s["flops"] == 2 * GRANITE_LAYER + 4 * 32 * 128 * 100 + logits
    # bf16 weights and the tied table, E4M3 keys and values of 100 tokens.
    assert s["bytes"] == 2 * (GRANITE_LAYER + 49155 * 4096) + 2 * 8 * 128 * 100
    assert len(s["gemms"]) == 8  # q, k, v, o, up, gate, down, logits


def test_granite_prefill_chunk_is_causal():
    s = work.prefill_chunk(GRANITE, start=256, n=256)
    pairs = 256 * 256 + 256 * 257 // 2
    assert s["flops"] == 2 * 256 * GRANITE_LAYER + 4 * 32 * 128 * pairs + 2 * 4096 * 49155


def test_moe_layer_counts_top_k_experts():
    assert sum(k * n for k, n in MOE.projections()) == MOE_ATTN + 8 * MOE_EXPERT
    # One token reads its eight experts; sixteen tokens may read all 32.
    assert MOE.layer_weights_read(1) == MOE_ATTN + 8 * MOE_EXPERT + 1024 * 32
    assert MOE.layer_weights_read(16) == MOE_ATTN + 32 * MOE_EXPERT + 1024 * 32


def test_roofline_takes_the_longer_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_s(1000.0, 10.0, peak) == pytest.approx(10.0)
    assert work.roofline_s(10.0, 1000.0, peak) == pytest.approx(100.0)
