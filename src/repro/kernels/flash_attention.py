"""Pallas TPU kernel: fused (flash) attention forward.

Online-softmax tiling: grid (B*H, Sq/bq, Sk/bk) with the Sk axis innermost;
q-tile [bq, D], k/v-tiles [bk, D] live in VMEM; the running (m, l)
statistics and the unnormalized f32 accumulator live in VMEM scratch across
the Sk axis, and the output tile is written once, normalized, on the last
step. Causal masking skips nothing structurally (TPU grids are dense) but
masks tile-internally; MXU-aligned defaults bq = bk = 128. D kept whole
(<= 256 for all our archs).

Used for ViT/DiT(S >= 256 tokens) and LM prefill; decode has its own kernel.

Bucketed serving (``core.bucketing``) feeds this kernel padded token axes:
an optional additive key ``bias`` [B, Sk] carries the ToMe proportional-
attention term (``log(sizes)``, ``-inf`` on pads) and an optional per-batch
``kv_len`` [B] masks keys past each member's real count — both reduce to the
same tile-internal masking the OOB guard already does, so padded keys get
exactly zero softmax weight.

Block layouts obey the TPU rule that a block's last two dims are (8, 128)
multiples or the array's full dims: the bias is passed as [B, 1, Sk] with
(1, 1, bk) blocks, indexed per batch member (no per-head copy), and
``kv_len`` sits whole in SMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(*refs, bq: int, bk: int, heads: int, sk_total: int, sq_total: int,
            causal: bool, scale: float, has_bias: bool, has_kvlen: bool):
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    bias_ref = next(it) if has_bias else None
    kvlen_ref = next(it) if has_kvlen else None
    o_ref, m_ref, l_ref, acc_ref = next(it), next(it), next(it), next(it)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)   # [bq, d]
    k = k_ref[0].astype(jnp.float32)   # [bk, d]
    v = v_ref[0].astype(jnp.float32)   # [bk, d]
    # sanitize OOB-padded kv rows of the last tile (their contents are
    # undefined; 0*NaN = NaN would otherwise leak through the p @ v product)
    krow = jax.lax.broadcasted_iota(jnp.int32, k.shape, 0) + j * bk
    kv_valid = krow < sk_total
    k = jnp.where(kv_valid, k, 0.0)
    v = jnp.where(kv_valid, v, 0.0)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # [bq, bk]
    if has_bias:
        # additive key bias (prop-attn log-sizes); clamp the pads' -inf to a
        # large finite negative so s stays NaN-free (exp still underflows to
        # exactly 0, which is the masking contract)
        s = s + jnp.maximum(bias_ref[0].astype(jnp.float32), _NEG_INF)  # [1, bk]
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bk
    mask = kpos < sk_total
    if has_kvlen:
        mask = jnp.logical_and(mask, kpos < kvlen_ref[pl.program_id(0) // heads])
    if causal:
        qpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i * bq
        mask = jnp.logical_and(mask, qpos + (sk_total - sq_total) >= kpos)
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]                                  # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    bias: jax.Array | None = None,
                    kv_len: jax.Array | None = None,
                    causal: bool = False, bq: int = 128, bk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q,k,v: [B, H, S, D] (equal head counts) -> [B, H, Sq, D].

    ``bias`` [B, Sk]: additive per-key logit bias (broadcast over heads and
    queries; the ToMe proportional-attention term). ``kv_len`` [B] int: real
    key count per batch member — keys at or past it are masked (padded
    bucket geometries). ``interpret=True`` runs the Pallas interpreter (CPU
    tests); the default compiles for the TPU.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(bq, sq)
    bk = min(bk, sk)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    grid = (b * h, pl.cdiv(sq, bq), pl.cdiv(sk, bk))
    operands = [qf, kf, vf]
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
        pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
        pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
    ]
    if bias is not None:
        operands.append(bias.astype(jnp.float32).reshape(b, 1, sk))
        in_specs.append(pl.BlockSpec((1, 1, bk), lambda g, i, j: (g // h, 0, j)))
    if kv_len is not None:
        operands.append(kv_len.astype(jnp.int32).reshape(b))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    kernel = functools.partial(_kernel, bq=bq, bk=bk, heads=h, sk_total=sk,
                               sq_total=sq, causal=causal,
                               scale=1.0 / math.sqrt(d),
                               has_bias=bias is not None,
                               has_kvlen=kv_len is not None)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(*operands)
    return out.reshape(b, h, sq, d)
