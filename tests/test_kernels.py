"""Per-kernel shape/dtype sweeps: Pallas in interpret mode vs pure-jnp oracles.

The raw kernel wrappers compile for the TPU by default; every call here
passes ``interpret=True`` so the sweep runs on the CPU."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.tome_scores import tome_scores

RNG = np.random.default_rng(42)


def _randn(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


@pytest.mark.parametrize("b,na,nb,d", [
    (1, 64, 64, 32), (2, 289, 288, 64), (1, 130, 100, 16), (3, 48, 49, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tome_scores_matches_ref(b, na, nb, d, dtype):
    a = _randn((b, na, d), dtype)
    bb = _randn((b, nb, d), dtype)
    a = a / jnp.linalg.norm(a.astype(jnp.float32), axis=-1, keepdims=True).astype(dtype)
    bb = bb / jnp.linalg.norm(bb.astype(jnp.float32), axis=-1, keepdims=True).astype(dtype)
    m, i = tome_scores(a, bb, bm=64, bn=64, interpret=True)
    mr, ir = ref.tome_scores_ref(a, bb)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr), atol=atol, rtol=1e-3)
    # argmax ties can legitimately differ: require the score at the kernel's
    # chosen index to equal the true row max
    scores = np.einsum("bnd,bmd->bnm", np.asarray(a, np.float32),
                       np.asarray(bb, np.float32))
    at_idx = np.take_along_axis(scores, np.asarray(i)[..., None], axis=-1)[..., 0]
    np.testing.assert_allclose(at_idx, scores.max(-1), atol=atol, rtol=1e-3)


@pytest.mark.parametrize("b,h,sq,sk,d", [
    (2, 3, 64, 64, 32), (1, 2, 100, 100, 16), (2, 2, 64, 128, 32),
    (1, 4, 257, 257, 64), (1, 1, 7, 200, 64),
])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_ref(b, h, sq, sk, d, causal):
    q = _randn((b, h, sq, d), jnp.float32)
    k = _randn((b, h, sk, d), jnp.float32)
    v = _randn((b, h, sk, d), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, bq=64, bk=64, interpret=True)
    expected = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("b,h,s,d", [(2, 2, 64, 32), (3, 1, 130, 16)])
def test_flash_attention_key_bias_matches_ref(b, h, s, d):
    """Additive per-key bias (ToMe prop-attn log-sizes; -inf marks bucket
    pads) against the jnp oracle."""
    q = _randn((b, h, s, d), jnp.float32)
    k = _randn((b, h, s, d), jnp.float32)
    v = _randn((b, h, s, d), jnp.float32)
    real = s - 7
    sizes = jnp.where(jnp.arange(s)[None, :] < real,
                      jnp.asarray(1.0 + RNG.uniform(size=(b, s)), jnp.float32),
                      0.0)
    bias = jnp.log(sizes)  # -inf on the padded tail
    out = flash_attention(q, k, v, bias=bias, bq=64, bk=64, interpret=True)
    expected = ref.flash_attention_ref(q, k, v, bias=bias)
    assert not np.any(np.isnan(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("b,h,s,d", [(3, 2, 64, 32), (2, 2, 100, 16)])
def test_flash_attention_kv_len_equals_truncation(b, h, s, d):
    """Per-batch kv_len masking must equal physically truncating the padded
    keys for the real queries."""
    q = _randn((b, h, s, d), jnp.float32)
    k = _randn((b, h, s, d), jnp.float32)
    v = _randn((b, h, s, d), jnp.float32)
    kv_len = jnp.asarray([s, s - 9, s // 2][:b], jnp.int32)
    out = flash_attention(q, k, v, kv_len=kv_len, bq=64, bk=64, interpret=True)
    expected = ref.flash_attention_ref(q, k, v, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=1e-4)
    for bi in range(b):
        n = int(kv_len[bi])
        trunc = ref.flash_attention_ref(q[bi:bi + 1, :, :n], k[bi:bi + 1, :, :n],
                                        v[bi:bi + 1, :, :n])
        np.testing.assert_allclose(np.asarray(out[bi:bi + 1, :, :n]),
                                   np.asarray(trunc), atol=2e-5, rtol=1e-4)


def test_flash_attention_bf16():
    q = _randn((1, 2, 128, 64), jnp.bfloat16)
    k = _randn((1, 2, 128, 64), jnp.bfloat16)
    v = _randn((1, 2, 128, 64), jnp.bfloat16)
    out = flash_attention(q, k, v, bq=64, bk=64, interpret=True)
    expected = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32), atol=3e-2)


@pytest.mark.parametrize("b,hq,hkv,s,d,length", [
    (2, 8, 2, 256, 32, 200), (1, 4, 4, 100, 64, 100),
    (3, 6, 2, 515, 16, 300), (2, 16, 1, 128, 64, 1), (1, 8, 8, 64, 128, 33),
])
def test_decode_attention_matches_ref(b, hq, hkv, s, d, length):
    q = _randn((b, hq, d), jnp.float32)
    k = _randn((b, s, hkv, d), jnp.float32)
    v = _randn((b, s, hkv, d), jnp.float32)
    out = decode_attention(q, k, v, jnp.int32(length), bs=128, interpret=True)
    expected = ref.decode_attention_ref(q, k, v, jnp.int32(length))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=1e-4)


def test_chunked_sdpa_matches_dense():
    from repro.models import layers as L
    q = _randn((2, 64, 8, 16), jnp.float32)
    k = _randn((2, 64, 2, 16), jnp.float32)
    v = _randn((2, 64, 2, 16), jnp.float32)
    dense = L.sdpa(q, k, v, causal=True)
    chunked = L.chunked_sdpa(q, k, v, causal=True, chunk_q=16)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense), atol=1e-5)
