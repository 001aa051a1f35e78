"""ToMe-style bipartite soft matching token merging (Bolya et al., ICLR'23).

This is the pruning *mechanism* behind Janus's collaboration-aware token
pruner: at a given layer we merge the ``r`` most similar (src→dst) token pairs,
reducing the token count by exactly ``r`` — a static-shape operation, which is
what makes the whole Janus schedule jit-compilable per (alpha) configuration.

The O(n^2 d) similarity + row-argmax is the compute hot-spot; a Pallas TPU
kernel implementing it lives in ``repro.kernels.tome_scores`` (this module is
the pure-jnp path and the oracle the kernel is tested against).

Token "sizes" track how many original patches each token represents; merging is
size-weighted averaging and attention can apply proportional log-size bias,
exactly as in ToMe.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class MergeIndices(NamedTuple):
    src_idx: jax.Array  # [B, r]    positions in the A (src) set that get merged
    unm_idx: jax.Array  # [B, Na-r] positions in the A set that survive (sorted)
    dst_idx: jax.Array  # [B, r]    destination in the B (dst) set per merged src


def bipartite_soft_matching(metric: jax.Array, r: int, *, protect_first: bool = True,
                            scores_fn=None) -> MergeIndices:
    """Compute which r tokens of the alternating A-set merge into the B-set.

    metric: [B, N, D] similarity metric (ToMe uses mean attention keys).
    ``scores_fn(a, b) -> (node_max, node_idx)`` may be supplied to use the
    Pallas kernel for the score+argmax computation.
    """
    b, n, d = metric.shape
    na = (n + 1) // 2
    if not 0 < r < na:
        raise ValueError(f"r={r} must be in (0, {na})")
    m = metric.astype(jnp.float32)
    m = m / (jnp.linalg.norm(m, axis=-1, keepdims=True) + 1e-6)
    a, bset = m[:, ::2], m[:, 1::2]
    if scores_fn is None:
        scores = jnp.einsum("bnd,bmd->bnm", a, bset)
        if protect_first:
            scores = scores.at[:, 0, :].set(-jnp.inf)
        node_max = scores.max(axis=-1)
        node_idx = scores.argmax(axis=-1)
    else:
        node_max, node_idx = scores_fn(a, bset)
        if protect_first:
            node_max = node_max.at[:, 0].set(-jnp.inf)
    order = jnp.argsort(-node_max, axis=-1)  # descending similarity
    src_idx = order[:, :r]
    unm_idx = jnp.sort(order[:, r:], axis=-1)  # keep original relative order (cls stays first)
    dst_idx = jnp.take_along_axis(node_idx, src_idx, axis=-1)
    return MergeIndices(src_idx, unm_idx, dst_idx)


def _merge_one(x, sizes, src_idx, unm_idx, dst_idx):
    a, bset = x[::2], x[1::2]
    sa, sb = sizes[::2], sizes[1::2]
    # size-weighted values
    aw = a * sa[:, None]
    bw = bset * sb[:, None]
    src_vals = jnp.take(aw, src_idx, axis=0)
    src_sizes = jnp.take(sa, src_idx, axis=0)
    b_new = bw.at[dst_idx].add(src_vals)
    sb_new = sb.at[dst_idx].add(src_sizes)
    # guard the divisor: real tokens always have sb_new >= 1 (bitwise no-op),
    # but padded execution (tome_merge_padded) carries size-0 pad tokens whose
    # 0/0 would otherwise mint NaNs that poison downstream attention
    # f32 sizes promote the weighted sums; the merged values return to x's
    # dtype so a bf16 model stays bf16
    dst = (b_new / jnp.maximum(sb_new, 1e-30)[:, None]).astype(x.dtype)
    unm = jnp.take(a, unm_idx, axis=0)
    s_unm = jnp.take(sa, unm_idx, axis=0)
    return jnp.concatenate([unm, dst], axis=0), jnp.concatenate([s_unm, sb_new], axis=0)


def merge_tokens(x: jax.Array, sizes: jax.Array, idx: MergeIndices):
    """Apply a computed matching. x: [B, N, D], sizes: [B, N] -> ([B, N-r, D], [B, N-r])."""
    return jax.vmap(_merge_one)(x, sizes, idx.src_idx, idx.unm_idx, idx.dst_idx)


def tome_merge(x: jax.Array, metric: jax.Array, sizes: jax.Array, r: int, *,
               protect_first: bool = True, scores_fn=None):
    """Full ToMe step: match on ``metric``, merge ``x``. Returns (x', sizes').
    Its ops carry the ``tome_merge`` name scope, so that a device trace can
    sum the merge's time."""
    if r <= 0:
        return x, sizes
    with jax.named_scope("tome_merge"):
        idx = bipartite_soft_matching(metric, r, protect_first=protect_first,
                                      scores_fn=scores_fn)
        return merge_tokens(x, sizes, idx)


def tome_merge_padded(x: jax.Array, metric: jax.Array, sizes: jax.Array,
                      r: int, *, protect_first: bool = True):
    """Pad-aware ToMe step for bucketed execution (``core.bucketing``).

    ``x`` carries real tokens first and padding tokens (``sizes == 0``) at the
    tail; per batch member the real count may differ, so pad handling is
    data-dependent (masks), never shape-dependent. Invariants that make the
    merge of the real tokens *identical* to ``tome_merge`` on the unpadded
    input:

      * pad columns of the score matrix are ``-inf`` — no real token can pick
        a pad as its merge destination;
      * pad rows' ``node_max`` is ``-inf`` — pads sort behind every real
        candidate, so the top-``r`` merged sources are always real tokens
        (the schedule's clamp guarantees r < real unprotected A-candidates);
      * after the merge, tokens are stably re-sorted so pads return to the
        tail — the next layer's alternating A/B assignment of the real
        tokens matches the unpadded run exactly.

    The caller is responsible for keeping pads out of *attention* (token
    sizes of 0 make the proportional-attention bias ``log(0) = -inf``, which
    zeroes their softmax weight exactly). Requires the pure-jnp scoring path:
    the Pallas ``scores_fn`` kernel has no pad-column masking. Its ops
    carry the ``tome_merge`` name scope, as ``tome_merge``'s do.
    """
    if r <= 0:
        return x, sizes
    with jax.named_scope("tome_merge"):
        b, n, d = metric.shape
        na = (n + 1) // 2
        if not 0 < r < na:
            raise ValueError(f"r={r} must be in (0, {na})")
        m = metric.astype(jnp.float32)
        m = m / (jnp.linalg.norm(m, axis=-1, keepdims=True) + 1e-6)
        a, bset = m[:, ::2], m[:, 1::2]
        pad_a = sizes[:, ::2] <= 0.0     # [B, Na]
        pad_b = sizes[:, 1::2] <= 0.0    # [B, Nb]
        scores = jnp.einsum("bnd,bmd->bnm", a, bset)
        scores = jnp.where(pad_b[:, None, :], -jnp.inf, scores)
        if protect_first:
            scores = scores.at[:, 0, :].set(-jnp.inf)
        node_max = jnp.where(pad_a, -jnp.inf, scores.max(axis=-1))
        node_idx = scores.argmax(axis=-1)
        order = jnp.argsort(-node_max, axis=-1, stable=True)
        src_idx = order[:, :r]
        unm_idx = jnp.sort(order[:, r:], axis=-1)
        dst_idx = jnp.take_along_axis(node_idx, src_idx, axis=-1)
        x, sizes = merge_tokens(x, sizes, MergeIndices(src_idx, unm_idx, dst_idx))
        # pads land mid-sequence (between the unmerged A-set and the B-set);
        # stably re-sort them to the tail so real-token order — and therefore the
        # next layer's A/B split — is exactly the unpadded run's
        tail = jnp.argsort((sizes <= 0.0).astype(jnp.int32), axis=-1, stable=True)
        x = jnp.take_along_axis(x, tail[:, :, None], axis=1)
        sizes = jnp.take_along_axis(sizes, tail, axis=1)
        return x, sizes
