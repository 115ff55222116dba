"""The program's model for a PVTv2 configuration: `PVTv2Config` and `PVTv2`
converted by `PVTv2.convert_from` from the family's dense weights, with the
shiftadd arm's routers and V-branch convolutions set from the same weights.
The only file of the family that imports the program. A program without
PVTv2 refuses the cell when the family is loaded, before any weights are
made."""
from __future__ import annotations

import dataclasses

from bench.lib.spec import SpecError

try:
    from repro.nn.pvt import PVTv2, PVTv2Config
except ImportError as e:
    raise SpecError(f"the program has no PVTv2 (repro.nn.pvt): {e}") from e


def build(cfg: dict, weights: dict):
    """(model, params) of the configuration's arm, from the tree that
    `weights.make` gives."""
    from repro.core.policy import DENSE, SHIFTADD

    policies = {"dense": DENSE, "shiftadd": SHIFTADD}
    pcfg = PVTv2Config(
        image_size=cfg["image_size"], in_channels=cfg["in_channels"],
        n_classes=cfg["n_classes"], widths=tuple(cfg["widths"]),
        depths=tuple(cfg["depths"]), heads=tuple(cfg["heads"]),
        mlp_ratios=tuple(cfg["mlp_ratios"]),
        sr_ratios=tuple(cfg["sr_ratios"]),
        patch_kernels=tuple(cfg["patch_kernels"]),
        patch_strides=tuple(cfg["patch_strides"]),
        dtype=cfg["activation_dtype"],
        moe_capacity=cfg.get("moe_capacity_factor", 1.25),
        policy=policies[cfg["policy"]])
    dense_model = PVTv2(dataclasses.replace(pcfg, policy=DENSE))
    model = PVTv2(pcfg)
    params = model.convert_from(dense_model, weights["dense"],
                                stage=cfg["convert_stage"])
    if cfg["policy"] == "shiftadd":
        caps = []
        for i, (stage, sp) in enumerate(zip(model.stages, params["stages"])):
            for j, blk in enumerate(sp["blocks"]):
                blk["feed"]["router"] = {"kernel": weights["router"][i][j]}
                blk["mixer"]["dwconv"] = weights["dwconv"][i][j]
            feed = stage["blocks"][0].feed
            caps.append(list(feed.capacity_plan(model.token_counts[i])[0]))
        if caps != [list(c) for c in cfg["moe_capacity_per_image"]]:
            raise SpecError(f"the program plans MoE capacities {caps} per "
                            f"image (one pair per stage); the configuration "
                            f"states {cfg['moe_capacity_per_image']}")
    return model, params
