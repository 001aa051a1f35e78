"""The served model of the ``vit`` family: the program's ``ViTConfig`` for a
sizes file, and the parameter layout the seeded weights are checked
against. Every import of the program for this family lives here, so that
the plain reference ``vit.py`` stays independent of it."""
from __future__ import annotations

from repro.models import vit


def model_config(c: dict, dtype) -> vit.ViTConfig:
    return vit.ViTConfig(
        img_res=c["image_size"], patch=c["patch_size"], n_layers=c["num_layers"],
        d_model=c["hidden_size"], n_heads=c["num_heads"], d_ff=c["mlp_dim"],
        n_classes=c["num_classes"], dtype=dtype,
        prop_attn=c["tome"]["proportional_attention"])


def param_specs(model_cfg: vit.ViTConfig) -> dict:
    return vit.specs(model_cfg)
