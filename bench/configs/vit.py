"""Plain reference for the ViT configurations: ViT (arXiv:2010.11929) with
Janus's mixed ToMe pruning schedule (Eq. 1) and bipartite soft matching
(ToMe, arXiv:2210.09461), in straightforward ``jax.numpy``.

It imports nothing of the program. It takes the weights the benchmark made
from the seed, the frame, and the served frame's pruning level ``alpha`` and
split, and computes the logits as the paper describes them:

* patch embedding, class token and learned position embedding;
* pre-norm blocks: LayerNorm (eps 1e-6), multi-head attention with ToMe's
  proportional attention (``+ log(size)`` on the keys), then the ToMe merge
  of ``r_l`` tokens, then LayerNorm and an MLP with GELU (tanh form, as in
  the original JAX ViT code);
* the head on the class token after a final LayerNorm;
* at an interior split, the activation is quantized to int8 with one scale
  (its largest magnitude) and back, as Janus sends it over the wire.

``precision`` is ``"f32"`` (float32 at the highest matmul precision: the
reference) or ``"int8"`` / ``"fp8"`` (every linear layer's input quantized
per token and its weight per output channel, symmetric int8 or float8
e4m3, products summed in float32: the control a lower-precision path would
be held against).

Besides the reference it gives the harness the family's geometry:
``num_tokens`` (the tokens that enter block 0) and ``frame_flops`` (the
operations one frame requires, counted from its shapes).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


# -- Janus Eq. 1 pruning schedule, clamped to ToMe's bipartite limit ---------

def merge_schedule(alpha: float, n_layers: int, n_tokens: int) -> tuple[int, ...]:
    """Tokens merged at each layer: floor(2^(alpha (N - l))) for l = 1..N
    (none at alpha = 0), clamped so that r < ceil(x/2) with the class token
    protected and at least two tokens left."""
    x, out = n_tokens, []
    for l in range(1, n_layers + 1):
        r = 0 if alpha == 0 else int(math.floor(2 ** (alpha * (n_layers - l))))
        r = max(0, min(r, (x + 1) // 2 - 1, x - 2))
        out.append(r)
        x -= r
    return tuple(out)


# -- geometry -----------------------------------------------------------------

def num_tokens(c: dict) -> int:
    """Tokens that enter block 0: the patches and the class token."""
    return (c["image_size"] // c["patch_size"]) ** 2 + 1


def frame_flops(cfg: dict, schedule) -> float:
    """Operations of one frame of configuration ``cfg`` (the sizes file's
    keys) whose block ``l`` merges ``schedule[l]`` tokens.

    Matrix products only, two operations per multiply-add: the patch
    embedding, per block the q/k/v/output projections, the attention scores
    and their weighted sum over the tokens that enter the block, the ToMe
    similarity scores of a merging block (its A tokens against its B tokens,
    on the head-mean keys), the MLP over the tokens left after the merge, and
    the head on the class token. Padding that a batched program adds is not
    counted: it is work the frame does not need."""
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


# -- layers -------------------------------------------------------------------

def _quant_rows(x):
    """Symmetric int8 fake quantization with one scale per row (last axis)."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _fp8_rows(x):
    """float8 (e4m3) fake quantization with one scale per row (last axis)."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(p, x, precision):
    w = p["w"]
    if precision in ("int8", "fp8"):
        q = _quant_rows if precision == "int8" else _fp8_rows
        x = q(x)
        w = q(w.T).T     # one scale per output channel
    return jnp.einsum("...i,io->...o", x, w) + p["b"]


def _layernorm(p, x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(p, x, sizes, n_heads, precision):
    """One frame: x [N, D], sizes [N] (0 marks an empty slot, which the
    ``log(size) = -inf`` bias keeps out of every softmax). Returns
    (out [N, D], keys [N, H, hd])."""
    n, d = x.shape
    hd = d // n_heads
    q = _linear({"w": p["wq"], "b": p["bq"]}, x, precision).reshape(n, n_heads, hd)
    k = _linear({"w": p["wk"], "b": p["bk"]}, x, precision).reshape(n, n_heads, hd)
    v = _linear({"w": p["wv"], "b": p["bv"]}, x, precision).reshape(n, n_heads, hd)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd) + jnp.log(sizes)[None, None, :]
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", w, v).reshape(n, d)
    return _linear({"w": p["wo"], "b": p["bo"]}, o, precision), k


def _merge(x, sizes, keys, r):
    """ToMe bipartite soft matching on one frame of N slots, of which the
    first hold tokens and the rest are empty (size 0). Alternate tokens form
    sets A (even) and B (odd); the class token (A's first) never merges;
    the r A tokens most similar (cosine of the head-mean keys) to some B
    token are averaged into it, weighted by size. The tokens are then laid
    out as A's survivors in their order, then B, then the empty slots."""
    n = x.shape[0]
    valid = sizes > 0
    m = keys.mean(axis=1)
    m = m / (jnp.linalg.norm(m, axis=-1, keepdims=True) + 1e-6)
    va, vb = valid[::2], valid[1::2]
    scores = jnp.where(vb[None, :], m[::2] @ m[1::2].T, -jnp.inf)
    scores = scores.at[0, :].set(-jnp.inf)
    best = jnp.where(va, scores.max(-1), -jnp.inf)
    dst = scores.argmax(-1)
    order = jnp.argsort(-best, stable=True)
    merged = jnp.zeros(order.shape, bool).at[order].set(jnp.arange(order.size) < r)
    xa, xb, sa, sb = x[::2], x[1::2], sizes[::2], sizes[1::2]
    wa = jnp.where(merged, sa, 0.0)
    sb_new = sb.at[dst].add(wa)
    xb_sum = (xb * sb[:, None]).at[dst].add(xa * wa[:, None])
    xb_new = jnp.where(vb[:, None], xb_sum / jnp.where(vb, sb_new, 1.0)[:, None], 0.0)
    keep_a = va & ~merged
    # back to one sequence: A slots at even positions, B at odd
    xs = jnp.zeros_like(x).at[::2].set(jnp.where(keep_a[:, None], xa, 0.0)).at[1::2].set(xb_new)
    ss = jnp.zeros_like(sizes).at[::2].set(jnp.where(keep_a, sa, 0.0)).at[1::2].set(sb_new)
    group = jnp.zeros(n, jnp.int32).at[::2].set(jnp.where(keep_a, 0, 2)) \
        .at[1::2].set(jnp.where(vb, 1, 2))
    perm = jnp.argsort(group * n + jnp.arange(n))
    return xs[perm], ss[perm]


def _payload_roundtrip(x):
    """Janus's wire format at an interior split: int8 with one scale."""
    s = jnp.max(jnp.abs(x))
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s * 127.0), -127, 127) / 127.0 * s


def embed(params: dict, sizes_cfg: dict, image, precision: str = "f32"):
    """Patch embedding, class token and position embedding: [N, D]."""
    p_ = sizes_cfg["patch_size"]
    h, w, c = image.shape
    patches = image.reshape(h // p_, p_, w // p_, p_, c).transpose(0, 2, 1, 3, 4)
    patches = patches.reshape((h // p_) * (w // p_), p_ * p_ * c)
    x = _linear(params["patch_embed"], patches, precision)
    return jnp.concatenate([params["cls"][0], x]) + params["pos"][0]


def block(bp: dict, sizes_cfg: dict, x, sizes, r, precision: str = "f32"):
    """One pre-norm block with the ToMe merge of ``r`` tokens after its
    attention. ``r`` may be traced: with r = 0 the tokens keep their order."""
    a, keys = _attention(bp["attn"], _layernorm(bp["ln1"], x), sizes,
                         sizes_cfg["num_heads"], precision)
    x = x + a
    xm, sm = _merge(x, sizes, keys, r)
    x, sizes = jnp.where(r > 0, xm, x), jnp.where(r > 0, sm, sizes)
    m = _linear(bp["mlp"]["fc1"], _layernorm(bp["ln2"], x), precision)
    x = x + _linear(bp["mlp"]["fc2"], _gelu(m), precision)
    return jnp.where((sizes > 0)[:, None], x, 0.0), sizes


def forward(params: dict, sizes_cfg: dict, image, schedule, split,
            precision: str = "f32"):
    """Logits [n_classes] of one frame ``image`` [H, W, C] with block ``l``
    merging ``schedule[l]`` tokens and the wire at ``split`` (0 and
    num_layers + 1: none). Merged tokens leave empty slots at the end of a
    sequence of fixed length, so every block has one shape and the blocks
    run as a scan over the stacked weights: one program for every
    schedule and split."""
    n_layers = sizes_cfg["num_layers"]
    x = embed(params, sizes_cfg, image, precision)
    sizes = jnp.ones(x.shape[0], jnp.float32)

    def step(carry, layer):
        x, sizes = carry
        bp, r, l = layer
        x = jnp.where((l == split) & (l > 0), _payload_roundtrip(x), x)
        return block(bp, sizes_cfg, x, sizes, r, precision), None

    (x, _), _ = jax.lax.scan(step, (x, sizes), (params["blocks"], schedule,
                                                 jnp.arange(n_layers)))
    x = jnp.where(split == n_layers, _payload_roundtrip(x), x)
    return _linear(params["head"], _layernorm(params["norm"], x)[0], precision)


_PROGRAMS: dict = {}


def _geometry(c: dict) -> tuple:
    return tuple(c[k] for k in ("image_size", "patch_size", "num_layers",
                                "hidden_size", "num_heads", "mlp_dim",
                                "num_classes"))


def init_params(sizes_cfg: dict, key, dtype):
    """Seeded weights in the layout ``forward`` reads (and the served model
    takes), made on the device in one program: linear weights
    N(0, 1/fan_in), biases, class token and position embedding N(0, 0.02^2),
    LayerNorm scales 1 + N(0, 0.02^2) and shifts N(0, 0.02^2). Every leaf is
    random, so no bias or norm parameter can be left out unseen."""
    _, p_, n_layers, d, _, f, n_cls = _geometry(sizes_cfg)
    pdim, n_tok = p_ * p_ * 3, num_tokens(sizes_cfg)
    shapes = {
        "patch_embed": {"w": (pdim, d), "b": (d,)},
        "cls": (1, 1, d), "pos": (1, n_tok, d),
        "blocks": {
            "ln1": {"scale": (n_layers, d), "bias": (n_layers, d)},
            "attn": {**{f"w{n}": (n_layers, d, d) for n in "qkvo"},
                     **{f"b{n}": (n_layers, d) for n in "qkvo"}},
            "ln2": {"scale": (n_layers, d), "bias": (n_layers, d)},
            "mlp": {"fc1": {"w": (n_layers, d, f), "b": (n_layers, f)},
                    "fc2": {"w": (n_layers, f, d), "b": (n_layers, d)}}},
        "norm": {"scale": (d,), "bias": (d,)},
        "head": {"w": (d, n_cls), "b": (n_cls,)},
    }
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))

    def make(key):
        out = []
        for (path, shape), k in zip(paths, jax.random.split(key, len(paths))):
            name = path[-1].key
            z = jax.random.normal(k, shape, jnp.float32)
            if name.startswith("w"):
                a = z / math.sqrt(shape[-2])
            elif name == "scale":
                a = 1.0 + 0.02 * z
            else:
                a = 0.02 * z
            out.append(a.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(key)


def logits(params: dict, sizes_cfg: dict, images, alpha: float, split: int,
           precision: str = "f32"):
    """Reference logits [B, n_classes] for frames ``images`` [B, H, W, C]
    that were all served at (``alpha``, ``split``), at the highest matmul
    precision; one program per (configuration, precision, batch)."""
    schedule = jnp.asarray(merge_schedule(alpha, sizes_cfg["num_layers"],
                                          num_tokens(sizes_cfg)), jnp.int32)
    key = (_geometry(sizes_cfg), precision)
    fn = _PROGRAMS.get(key)
    if fn is None:
        def run(p, ims, schedule, split):
            p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
            return jax.vmap(lambda im: forward(p, sizes_cfg, im, schedule,
                                               split, precision))(ims)
        fn = _PROGRAMS[key] = jax.jit(run)
    with jax.default_matmul_precision("highest"):
        return fn(params, images.astype(jnp.float32), schedule, jnp.int32(split))
