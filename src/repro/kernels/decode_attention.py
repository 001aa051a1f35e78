"""Pallas TPU kernel: single-position GQA decode attention over a KV cache.

The serving hot loop: one query token per sequence attends over a long cache.
Memory-bound (the whole cache streams HBM->VMEM once); the kernel fuses the
masked online-softmax so nothing but q, per-tile kv and the [g, D]
accumulator lives in VMEM.

Layout: q [B, Hq, D]; cache k/v [B, S, Hkv, D], streamed head-major as
[B, Hkv, S, D] so every block's last two dims are (tile, D) — a TPU block's
last two dims must be (8, 128) multiples or the array's full dims. Grid
(B, Hkv, S/bs) with the cache axis innermost. Each (batch, kv-head) program
streams its cache slice and serves its group of Hq/Hkv query heads at once;
the running (m, l) statistics and the accumulator live in VMEM scratch.
Valid length masks tile-internally (cache buffers are fixed-capacity); the
per-batch lengths sit whole in SMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bs: int, s_total: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)      # [g, d]
    k = k_ref[0, 0].astype(jnp.float32)      # [bs, d]
    v = v_ref[0, 0].astype(jnp.float32)      # [bs, d]
    length = len_ref[pl.program_id(0)]

    krow = jax.lax.broadcasted_iota(jnp.int32, k.shape, 0) + j * bs
    row_valid = jnp.logical_and(krow < length, krow < s_total)
    k = jnp.where(row_valid, k, 0.0)
    v = jnp.where(row_valid, v, 0.0)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [g, bs]
    s = s * (1.0 / math.sqrt(q.shape[-1]))
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bs
    valid = jnp.logical_and(kpos < length, kpos < s_total)
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = m_ref[...]                                   # [g, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     length: jax.Array, *, bs: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: [B, Hq, D]; k,v: [B, S, Hkv, D]; length: scalar valid cache length.
    ``interpret=True`` runs the Pallas interpreter (CPU tests); the default
    compiles for the TPU."""
    b, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    bs = min(bs, s)
    qg = q.reshape(b, hkv, g, d)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    grid = (b, hkv, pl.cdiv(s, bs))
    kernel = functools.partial(_kernel, bs=bs, s_total=s)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, d), lambda b_, h, j: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d), lambda b_, h, j: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, bs, d), lambda b_, h, j: (b_, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, h, j: (b_, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(lengths, qg, kt, vt)
    return out.reshape(b, hq, d)
