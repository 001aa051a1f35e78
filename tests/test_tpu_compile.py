"""The Pallas kernels compile for a TPU v5e at ViT-L@384 widths.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) ``v5e:2x2`` topology, which catches what interpret mode cannot —
block shapes the Mosaic lowering refuses, VMEM overuse. The topology is
described inside a module-scoped fixture, never at import time: only one
process may load the TPU library, and the test workers all import this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.janus_vit_l384 import CONFIG as VIT_L384
from repro.core.bucketing import BucketingConfig, BucketTable
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.tome_scores import tome_scores

BATCH = 8  # a fleet micro-batch


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on one described chip, with the persistent compile cache
    off: entries compiled for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, not a fallback


def _bucket_edge() -> int:
    """The smallest cloud-entry bucket edge at mid split for ViT-L@384 with
    two edges per split, as ``--bucket-edges 2`` builds it."""
    table = BucketTable.build(VIT_L384, np.arange(0.0, 0.31, 0.01),
                              config=BucketingConfig(n_edges=2))
    return table.edges_by_split[VIT_L384.n_layers // 2][0]


@pytest.mark.parametrize("tokens", ["full", "bucket_edge"])
def test_flash_attention_compiles_for_v5e(one_chip, tokens):
    cfg = VIT_L384
    s = cfg.num_tokens if tokens == "full" else _bucket_edge()
    assert s % 128 != 0   # a ragged last tile, as the served shapes have
    qkv = ((BATCH, cfg.n_heads, s, cfg.head_dim), jnp.bfloat16)
    _compile(lambda q, k, v, bias, kv_len: flash_attention(
                 q, k, v, bias=bias, kv_len=kv_len),
             one_chip, qkv, qkv, qkv, ((BATCH, s), jnp.float32),
             ((BATCH,), jnp.int32))


def test_tome_scores_compiles_for_v5e(one_chip):
    na = (VIT_L384.num_tokens + 1) // 2
    nb = VIT_L384.num_tokens // 2
    _compile(tome_scores, one_chip,
             ((BATCH, na, VIT_L384.head_dim), jnp.float32),
             ((BATCH, nb, VIT_L384.head_dim), jnp.float32))


@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (2, 8, 2, 256, 32), (1, 4, 4, 100, 64), (3, 6, 2, 515, 16),
    (2, 16, 1, 128, 64), (1, 8, 8, 64, 128),
])
def test_decode_attention_compiles_for_v5e(one_chip, b, hq, hkv, s, d):
    _compile(lambda q, k, v, n: decode_attention(q, k, v, n, bs=128),
             one_chip, ((b, hq, d), jnp.float32), ((b, s, hkv, d), jnp.float32),
             ((b, s, hkv, d), jnp.float32), ((), jnp.int32))
