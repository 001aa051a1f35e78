"""Pallas TPU kernel: ToMe bipartite cosine scores + streaming row-argmax.

The ToMe hot spot is an O(Na·Nb·D) similarity matrix whose only consumer is a
per-row (max, argmax) — materializing the [Na, Nb] matrix in HBM wastes both
bandwidth and memory. This kernel computes scores tile-by-tile on the MXU and
keeps only the running (max, argmax) per row in VMEM — the same online
reduction trick flash-attention uses for softmax, applied to argmax.

Tiling: grid (B, Na/bm, Nb/bn); a-tile [bm, D] and b-tile [bn, D] in VMEM, D is
kept whole (metric dims are <= head_dim-scale). Each tile is scored
transposed, [bn, bm], so the per-row reduction lands along lanes as a
[1, bm] row: the two outputs are [B, 1, Na] with (1, 1, bm) blocks (a TPU
block's last two dims must be (8, 128) multiples or the full dims), and they
revisit the same VMEM block across the Nb axis (innermost grid dim).
MXU-aligned defaults bm = bn = 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, max_ref, idx_ref, *, bn: int, nb_total: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        max_ref[...] = jnp.full_like(max_ref, -jnp.inf)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    a = a_ref[0].astype(jnp.float32)          # [bm, d]
    b = b_ref[0].astype(jnp.float32)          # [bn, d]
    scores = jax.lax.dot_general(b, a, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [bn, bm]
    # mask padding rows (b tokens past Nb) in the final tile
    row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0) + j * bn
    scores = jnp.where(row < nb_total, scores, -jnp.inf)

    local_max = jnp.max(scores, axis=0, keepdims=True)            # [1, bm]
    # first-occurrence argmax, as jnp.argmax breaks ties
    local_idx = jnp.min(jnp.where(scores == local_max, row, nb_total),
                        axis=0, keepdims=True)

    run_max = max_ref[0]
    take_new = local_max > run_max
    max_ref[0] = jnp.where(take_new, local_max, run_max)
    idx_ref[0] = jnp.where(take_new, local_idx, idx_ref[0])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def tome_scores(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
                interpret: bool = False):
    """a: [B, Na, D], b: [B, Nb, D] -> (node_max [B, Na] f32, node_idx int32).

    ``interpret=True`` runs the Pallas interpreter (CPU tests); the default
    compiles for the TPU."""
    B, na, d = a.shape
    nb = b.shape[1]
    bm = min(bm, na)
    bn = min(bn, nb)
    grid = (B, pl.cdiv(na, bm), pl.cdiv(nb, bn))
    kernel = functools.partial(_kernel, bn=bn, nb_total=nb)
    out_max, out_idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, d), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, bn, d), lambda b_, i, j: (b_, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bm), lambda b_, i, j: (b_, 0, i)),
            pl.BlockSpec((1, 1, bm), lambda b_, i, j: (b_, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, na), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, na), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="tome_scores",
    )(a, b)
    return out_max.reshape(B, na), out_idx.reshape(B, na)
