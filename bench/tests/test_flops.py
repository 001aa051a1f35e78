"""The ViT family's FLOP count against a hand count, and the peaks table."""
import json

import pytest

import flops
import harness

VIT_L = json.loads((harness.BENCH / "configs" / "vit-l384.json").read_text())
VIT = harness.load_module(harness.BENCH / "configs" / "vit.py")


def test_vit_l384_unpruned_blocks_by_hand():
    n, d, f = 577, 1024, 4096
    per_block = 8 * n * d * d + 4 * n * n * d + 4 * n * d * f
    embed, head = 2 * 576 * 768 * d, 2 * d * 1000
    assert per_block * 24 == 381_224_583_168          # 381.2 GFLOP in the blocks
    assert VIT.frame_flops(VIT_L, [0] * 24) == embed + per_block * 24 + head


def test_a_merging_block_counts_tome_scores_and_a_smaller_mlp():
    n, d, f, hd, r = 577, 1024, 4096, 64, 74
    first = 8 * n * d * d + 4 * n * n * d + 2 * 289 * 288 * hd + 4 * (n - r) * d * f
    sched = [r] + [0] * 23
    rest = 23 * (8 * (n - r) * d * d + 4 * (n - r) ** 2 * d + 4 * (n - r) * d * f)
    embed, head = 2 * 576 * 768 * d, 2 * d * 1000
    assert VIT.frame_flops(VIT_L, sched) == embed + first + rest + head


def test_peaks_know_the_v5e_and_refuse_others():
    assert flops.peak("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        flops.peak("TPU v9 imaginary")
