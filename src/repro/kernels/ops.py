"""Jit'd public wrappers for the Pallas kernels.

The raw kernels compile for the TPU by default (``interpret=False``). These
wrappers choose for the caller: ``interpret`` is set exactly when JAX's
default backend is not a TPU, so on a CPU they run the Pallas interpreter
(correctness only, never a timing). ``use_pallas=False`` selects the
pure-jnp oracle in ``ref.py``. No model calls these wrappers yet: the
served ViT runs jnp attention and jnp ToMe.

Every wrapper keeps the oracle's exact signature so tests can sweep
shapes/dtypes with assert_allclose against ref.py.
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.tome_scores import tome_scores as _tome_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def tome_scores(a, b, *, use_pallas: bool | None = None):
    """(node_max, node_idx) for ToMe bipartite matching."""
    if use_pallas is None:
        use_pallas = True
    if use_pallas:
        return _tome_pallas(a, b, interpret=not _on_tpu())
    return ref.tome_scores_ref(a, b)


def flash_attention(q, k, v, *, bias=None, kv_len=None, causal: bool = False,
                    use_pallas: bool | None = None):
    """``bias`` [B, Sk] adds a per-key logit term (prop-attn log-sizes);
    ``kv_len`` [B] masks keys past each member's real count (bucket pads)."""
    if use_pallas is None:
        use_pallas = True
    if use_pallas:
        return _flash_pallas(q, k, v, bias=bias, kv_len=kv_len, causal=causal,
                             interpret=not _on_tpu())
    return ref.flash_attention_ref(q, k, v, bias=bias, kv_len=kv_len,
                                   causal=causal)


def decode_attention(q, k, v, length, *, use_pallas: bool | None = None):
    if use_pallas is None:
        use_pallas = True
    if use_pallas:
        return _decode_pallas(q, k, v, length, interpret=not _on_tpu())
    return ref.decode_attention_ref(q, k, v, length)


def tome_scores_fn(use_pallas: bool = True):
    """A ``scores_fn`` suitable for core.tome.bipartite_soft_matching."""
    def fn(a, b):
        return tome_scores(a, b, use_pallas=use_pallas)
    return fn
