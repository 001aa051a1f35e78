"""The reference's fixed-shape scan against the same model written out
directly: each block a different shape, merged tokens dropped."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness

VIT = harness.load_module(harness.BENCH / "configs" / "vit.py")
CFG = dict(image_size=64, patch_size=16, hidden_size=32, mlp_dim=64, num_heads=4,
           num_layers=4, num_classes=7)


def direct_merge(x, sizes, keys, r):
    m = keys.mean(axis=1)
    m = m / (jnp.linalg.norm(m, axis=-1, keepdims=True) + 1e-6)
    scores = (m[::2] @ m[1::2].T).at[0, :].set(-jnp.inf)
    order = jnp.argsort(-scores.max(-1), stable=True)
    src, keep = order[:r], jnp.sort(order[r:])
    dst = scores.argmax(-1)[src]
    xa, xb, sa, sb = x[::2], x[1::2], sizes[::2], sizes[1::2]
    sb_new = sb.at[dst].add(sa[src])
    xb_new = (xb * sb[:, None]).at[dst].add(xa[src] * sa[src][:, None]) / sb_new[:, None]
    return jnp.concatenate([xa[keep], xb_new]), jnp.concatenate([sa[keep], sb_new])


def direct_forward(p, image, schedule, split):
    x = VIT.embed(p, CFG, image)
    sizes = jnp.ones(x.shape[0])
    for l, r in enumerate(schedule):
        if l == split and l > 0:
            x = VIT._payload_roundtrip(x)
        bp = jax.tree.map(lambda a, l=l: a[l], p["blocks"])
        a, keys = VIT._attention(bp["attn"], VIT._layernorm(bp["ln1"], x), sizes,
                                 CFG["num_heads"], "f32")
        x = x + a
        if r:
            x, sizes = direct_merge(x, sizes, keys, r)
        h = VIT._linear(bp["mlp"]["fc1"], VIT._layernorm(bp["ln2"], x), "f32")
        x = x + VIT._linear(bp["mlp"]["fc2"], VIT._gelu(h), "f32")
    if split == len(schedule):
        x = VIT._payload_roundtrip(x)
    return VIT._linear(p["head"], VIT._layernorm(p["norm"], x)[0], "f32")


@pytest.mark.parametrize("alpha,split", [(0.0, 0), (0.9, 0), (1.5, 5), (0.9, 2), (1.5, 4)])
def test_scan_reference_matches_the_direct_model(alpha, split):
    params = VIT.init_params(CFG, jax.random.key(3), jnp.float32)
    images = jax.random.normal(jax.random.key(4), (2, 64, 64, 3))
    schedule = VIT.merge_schedule(alpha, 4, 17)
    assert alpha == 0 or sum(schedule) > 0
    got = np.asarray(VIT.logits(params, CFG, images, alpha, split))
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(direct_forward(params, im, schedule, split))
                         for im in images])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_schedule_follows_eq1_and_the_bipartite_clamp():
    assert VIT.merge_schedule(0.0, 24, 577) == (0,) * 24
    s = VIT.merge_schedule(0.27, 24, 577)
    assert s[:3] == (74, 61, 50) and s[-1] == 1
    # a steep alpha is clamped: never more than half the tokens less one
    x = 17
    for r in VIT.merge_schedule(3.0, 4, 17):
        assert r <= (x + 1) // 2 - 1
        x -= r
