"""Operations a served ViT frame requires, counted from its shapes.

Matrix products only, two operations per multiply-add: the patch embedding,
per block the q/k/v/output projections, the attention scores and their
weighted sum over the tokens that enter the block, the ToMe similarity
scores of a merging block (its A tokens against its B tokens, on the
head-mean keys), the MLP over the tokens left after the merge, and the head
on the class token. Padding that a batched program adds is not counted:
it is work the frame does not need.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def frame_flops(cfg: dict, schedule) -> float:
    """Operations of one frame of configuration ``cfg`` (the sizes file's
    keys) whose block ``l`` merges ``schedule[l]`` tokens."""
    d, f = cfg["hidden_size"], cfg["mlp_dim"]
    hd = d // cfg["num_heads"]
    p = cfg["patch_size"]
    n_patch = (cfg["image_size"] // p) ** 2
    total = 2.0 * n_patch * (p * p * 3) * d
    n = n_patch + 1
    for r in schedule:
        total += 2.0 * n * d * d * 4          # q, k, v, output projections
        total += 2.0 * n * n * d * 2          # scores and weighted values
        if r:
            total += 2.0 * ((n + 1) // 2) * (n // 2) * hd
        n -= r
        total += 2.0 * n * d * f * 2          # MLP
    return total + 2.0 * d * cfg["num_classes"]


def peak(device_kind: str, key: str = "bf16_flops_per_s") -> float:
    """The chip's published peak; a device not in the table is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}; "
                       f"known: {sorted(table)}")
    return float(table[device_kind][key])
