"""Vision Transformer (ViT) — the paper's model family.

Two execution paths:

* ``forward``          — stacked-params ``lax.scan`` over layers (fast compile;
                         used for training and vanilla serving).
* ``forward_janus``    — unrolled blocks with a static per-layer ToMe merge
                         schedule and an optional layer range ``[start, end)``
                         so the Janus engine can run the *device partition* and
                         the *cloud partition* as separate programs. Token
                         counts shrink layer-by-layer per the schedule — all
                         shapes static for a given (alpha) configuration.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core import tome
from repro.models import layers as L
from repro.models.param import ParamSpec
from repro.runtime.flags import layer_unroll


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    img_res: int = 224
    patch: int = 16
    n_layers: int = 12
    d_model: int = 768
    n_heads: int = 12
    d_ff: int = 3072
    n_classes: int = 1000
    in_channels: int = 3
    dtype: Any = jnp.float32
    prop_attn: bool = True  # ToMe proportional attention when pruning
    remat: bool = False
    fused_qkv: bool = False  # single fused QKV matmul (serving optimization)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def grid(self) -> int:
        return self.img_res // self.patch

    @property
    def num_patches(self) -> int:
        return self.grid * self.grid

    @property
    def num_tokens(self) -> int:
        return self.num_patches + 1  # + cls


def _block_specs(cfg: ViTConfig) -> dict:
    return {
        "ln1": L.layernorm_specs(cfg.d_model),
        "attn": L.attention_specs(cfg.d_model, cfg.n_heads, cfg.n_heads,
                                  cfg.head_dim, bias=True,
                                  fused_qkv=cfg.fused_qkv),
        "ln2": L.layernorm_specs(cfg.d_model),
        "mlp": L.mlp_specs(cfg.d_model, cfg.d_ff),
    }


def specs(cfg: ViTConfig) -> dict:
    pdim = cfg.patch * cfg.patch * cfg.in_channels
    return {
        "patch_embed": L.linear_specs(pdim, cfg.d_model, axes=("patch", "embed")),
        "cls": ParamSpec((1, 1, cfg.d_model), (None, None, "embed"), init="normal"),
        "pos": ParamSpec((1, cfg.num_tokens, cfg.d_model), (None, "pos", "embed"), init="normal"),
        "blocks": L.stack_specs(cfg.n_layers, lambda: _block_specs(cfg)),
        "norm": L.layernorm_specs(cfg.d_model),
        "head": L.linear_specs(cfg.d_model, cfg.n_classes, axes=("embed", "vocab")),
    }


def patchify(cfg: ViTConfig, images: jax.Array) -> jax.Array:
    """[B, H, W, C] -> [B, N, P*P*C]"""
    b, h, w, c = images.shape
    p = cfg.patch
    x = images.reshape(b, h // p, p, w // p, p, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (h // p) * (w // p), p * p * c)


def embed_tokens(params: dict, cfg: ViTConfig, images: jax.Array) -> jax.Array:
    x = L.linear(params["patch_embed"], patchify(cfg, images).astype(cfg.dtype))
    cls = jnp.broadcast_to(params["cls"].astype(x.dtype), (x.shape[0], 1, cfg.d_model))
    x = jnp.concatenate([cls, x], axis=1)
    return x + params["pos"].astype(x.dtype)


def initial_sizes(x: jax.Array) -> jax.Array:
    """ToMe token sizes [B, N], one patch per token. Always float32, whatever
    the model dtype: bf16 holds integers exactly only up to 256, and merged
    sizes are summed and fed to ``log`` for proportional attention."""
    return jnp.ones(x.shape[:2], jnp.float32)


def _block(bp: dict, cfg: ViTConfig, x: jax.Array, sizes: jax.Array | None = None,
           merge_r: int = 0, scores_fn=None):
    bias = None
    if sizes is not None and cfg.prop_attn:
        bias = jnp.log(sizes.astype(jnp.float32))
    attn_out, _, metric = L.attention(
        bp["attn"], L.layernorm(bp["ln1"], x), n_heads=cfg.n_heads, n_kv=cfg.n_heads,
        head_dim=cfg.head_dim, bias=bias, return_metric=True)
    x = x + attn_out
    if merge_r > 0:
        assert sizes is not None
        x, sizes = tome.tome_merge(x, metric, sizes, merge_r, scores_fn=scores_fn)
    x = x + L.mlp(bp["mlp"], L.layernorm(bp["ln2"], x))
    return x, sizes


def forward(params: dict, cfg: ViTConfig, images: jax.Array) -> jax.Array:
    """Vanilla forward: scan over stacked blocks. Returns logits [B, n_classes]."""
    x = embed_tokens(params, cfg, images)

    def body(carry, bp):
        y, _ = _block(bp, cfg, carry)
        return y, None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["blocks"], unroll=layer_unroll(cfg.n_layers))
    x = L.layernorm(params["norm"], x)
    return L.linear(params["head"], x[:, 0])


def layer_params(params: dict, l: int) -> dict:
    """Slice the stacked block params for unrolled (Janus) execution."""
    return jax.tree.map(lambda a: a[l], params["blocks"])


def run_blocks(params: dict, cfg: ViTConfig, x: jax.Array, sizes: jax.Array,
               schedule: Sequence[int], start: int, end: int, scores_fn=None):
    """Run blocks [start, end) with per-layer merge counts ``schedule[l]``.

    Token count entering layer l is static: num_tokens - sum(schedule[:l]).
    Returns (x, sizes).
    """
    assert len(schedule) == cfg.n_layers
    from repro.sharding import constrain
    for l in range(start, end):
        x, sizes = _block(layer_params(params, l), cfg, x, sizes,
                          merge_r=int(schedule[l]), scores_fn=scores_fn)
        # keep [batch(dp), tokens, d(replicated)] stable across the unrolled
        # merge layers — without this GSPMD reshards around every
        # argsort/gather (§Perf v1 regression)
        x = constrain(x, ("batch", None, None))
        sizes = constrain(sizes, ("batch", None))
    return x, sizes


def _block_padded(bp: dict, cfg: ViTConfig, x: jax.Array, sizes: jax.Array,
                  merge_r: int = 0):
    """Pad-aware block for bucketed execution (``core.bucketing``).

    ``sizes == 0`` marks padding tokens (always at the tail on entry). The
    masking is *exact*, not approximate: pad keys get an additive ``-inf``
    attention bias — ``log(0)`` when proportional attention supplies the bias
    anyway, an explicit 0/-inf mask otherwise — so their softmax weight is
    exactly zero and real-token outputs equal the unpadded block's up to
    XLA reduction-order (sub-ulp) effects. Merging goes through
    ``tome.tome_merge_padded`` which keeps pads out of the matching and
    restores them to the tail.
    """
    s32 = sizes.astype(jnp.float32)
    if cfg.prop_attn:
        bias = jnp.log(s32)  # pads: log(0) = -inf
    else:
        bias = jnp.where(s32 > 0.0, 0.0, -jnp.inf)
    attn_out, _, metric = L.attention(
        bp["attn"], L.layernorm(bp["ln1"], x), n_heads=cfg.n_heads, n_kv=cfg.n_heads,
        head_dim=cfg.head_dim, bias=bias, return_metric=True)
    x = x + attn_out
    if merge_r > 0:
        x, sizes = tome.tome_merge_padded(x, metric, sizes, merge_r)
    x = x + L.mlp(bp["mlp"], L.layernorm(bp["ln2"], x))
    return x, sizes


def run_blocks_padded(params: dict, cfg: ViTConfig, x: jax.Array, sizes: jax.Array,
                      schedule: Sequence[int], start: int, end: int):
    """Pad-aware ``run_blocks``: same contract, but tail tokens with
    ``sizes == 0`` are carried through every layer as inert padding. Token
    count entering layer l is still static (bucket edge minus merges so far);
    the *real* token count per batch member is data, not shape."""
    assert len(schedule) == cfg.n_layers
    from repro.sharding import constrain
    for l in range(start, end):
        x, sizes = _block_padded(layer_params(params, l), cfg, x, sizes,
                                 merge_r=int(schedule[l]))
        x = constrain(x, ("batch", None, None))
        sizes = constrain(sizes, ("batch", None))
    return x, sizes


def head_apply(params: dict, cfg: ViTConfig, x: jax.Array) -> jax.Array:
    x = L.layernorm(params["norm"], x)
    return L.linear(params["head"], x[:, 0])


def forward_janus(params: dict, cfg: ViTConfig, images: jax.Array,
                  schedule: Sequence[int], split: int | None = None,
                  scores_fn=None):
    """Full Janus forward (device+cloud fused, for correctness testing).

    ``split`` only matters for the engine, which calls the partition functions
    separately; here it is accepted so tests can confirm split-at-s equals the
    monolithic run for any s.
    """
    x = embed_tokens(params, cfg, images)
    sizes = initial_sizes(x)
    x, sizes = run_blocks(params, cfg, x, sizes, schedule, 0, cfg.n_layers, scores_fn=scores_fn)
    return head_apply(params, cfg, x)


def token_counts(cfg: ViTConfig, schedule: Sequence[int]) -> list[int]:
    """Tokens *entering* each layer l (length n_layers + 1; last = output count)."""
    counts = [cfg.num_tokens]
    for r in schedule:
        counts.append(counts[-1] - int(r))
    return counts
