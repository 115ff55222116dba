"""The program's model for a ViT configuration: `ViTConfig` and `ShiftAddViT`
converted by `build_policy_model` from the family's dense weights, with the
shiftadd arm's router and V-branch convolution set from the same weights.
The only file of the family that imports the program."""
from __future__ import annotations

import dataclasses

from bench.lib.spec import SpecError


def build(cfg: dict, weights: dict):
    """(model, params) of the configuration's arm, from the tree that
    `weights.make` gives."""
    from repro.core.policy import DENSE
    from repro.nn.vit import ShiftAddViT, ViTConfig
    from repro.serve.vision import build_policy_model

    vcfg = ViTConfig(image_size=cfg["image_size"], patch_size=cfg["patch_size"],
                     in_channels=cfg["in_channels"], n_classes=cfg["n_classes"],
                     n_layers=cfg["n_layers"], d_model=cfg["d_model"],
                     n_heads=cfg["n_heads"], d_ff=cfg["d_ff"],
                     dtype=cfg["activation_dtype"],
                     moe_capacity=cfg.get("moe_capacity_factor", 1.25))
    dense_model = ShiftAddViT(dataclasses.replace(vcfg, policy=DENSE))
    model, params = build_policy_model(vcfg, cfg["policy"], dense_model,
                                       weights["dense"])
    if cfg["policy"] == "shiftadd":
        for i, blk in enumerate(params["blocks"]):
            blk["feed"]["router"] = {"kernel": weights["router"][i]}
            blk["mixer"]["dwconv"] = weights["dwconv"][i]
        caps = model.blocks[0].feed.capacity_plan(vcfg.n_patches)[0]
        if list(caps) != list(cfg["moe_capacity_per_image"]):
            raise SpecError(f"the program plans MoE capacities {caps} per "
                            f"image; the configuration states "
                            f"{cfg['moe_capacity_per_image']}")
    return model, params
