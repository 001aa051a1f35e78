"""A configuration's family supplies its model geometry: the served config,
the tokens that enter block 0 and a frame's operations. For the ViT sizes
files these equal the harness's own ViT formulas, kept here as constants,
and a family's plain reference imports nothing of the program."""
import ast
import json

import jax.numpy as jnp
import pytest

import harness
from repro.core import planner
from repro.models import vit

BENCHMARK = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
VIT = harness.load_module(harness.BENCH / "configs" / "vit.py")
VIT_SERVED = harness.load_module(harness.BENCH / "configs" / "vit_served.py")
SIZES = {"vit-l384.json": 577, "vit-b16.json": 197}


def sizes(name):
    return json.loads((harness.BENCH / "configs" / name).read_text())


def vit_config(c, dtype):
    return vit.ViTConfig(
        img_res=c["image_size"], patch=c["patch_size"], n_layers=c["num_layers"],
        d_model=c["hidden_size"], n_heads=c["num_heads"], d_ff=c["mlp_dim"],
        n_classes=c["num_classes"], dtype=dtype,
        prop_attn=c["tome"]["proportional_attention"])


def vit_frame_flops(cfg, schedule):
    d, f = cfg["hidden_size"], cfg["mlp_dim"]
    hd = d // cfg["num_heads"]
    p = cfg["patch_size"]
    n_patch = (cfg["image_size"] // p) ** 2
    total = 2.0 * n_patch * (p * p * 3) * d
    n = n_patch + 1
    for r in schedule:
        total += 2.0 * n * d * d * 4
        total += 2.0 * n * n * d * 2
        if r:
            total += 2.0 * ((n + 1) // 2) * (n // 2) * hd
        n -= r
        total += 2.0 * n * d * f * 2
    return total + 2.0 * d * cfg["num_classes"]


@pytest.mark.parametrize("name", sorted(SIZES))
def test_served_config_is_the_vit_config_field_for_field(name):
    c = sizes(name)
    dtype = jnp.dtype(c["dtype"])
    got = VIT_SERVED.model_config(c, dtype)
    assert got == vit_config(c, dtype)
    assert VIT_SERVED.param_specs(got) == vit.specs(vit_config(c, dtype))


@pytest.mark.parametrize("name", sorted(SIZES))
def test_token_count_is_patches_and_class_token(name):
    c = sizes(name)
    assert VIT.num_tokens(c) == SIZES[name] == (c["image_size"] // c["patch_size"]) ** 2 + 1
    assert VIT.num_tokens(c) == vit_config(c, jnp.float32).num_tokens


@pytest.mark.parametrize("name", sorted(SIZES))
def test_frame_flops_are_unchanged_at_every_alpha_of_the_planner(name):
    c = sizes(name)
    n_layers, n_tok = c["num_layers"], SIZES[name]
    grid = planner.default_alpha_grid(n_layers, n_tok, 0.01)
    assert len(grid) > 10 and grid[0] == 0.0
    for alpha in grid:
        schedule = VIT.merge_schedule(alpha, n_layers, n_tok)
        assert VIT.frame_flops(c, schedule) == vit_frame_flops(c, schedule), alpha


@pytest.mark.parametrize("family", sorted(
    {json.loads((harness.ROOT / c["file"]).read_text())["family"] for c in BENCHMARK["configs"]}))
def test_a_family_reference_imports_nothing_of_the_program(family):
    path = harness.BENCH / "configs" / f"{family}.py"
    tree = ast.parse(path.read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module or "")
    assert not {m for m in imported if m.split(".")[0] == "repro"}, imported
    assert "repro" not in path.read_text()
    assert (harness.BENCH / "configs" / f"{family}_served.py").is_file()
