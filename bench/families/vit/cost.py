"""Operations and kernel calls of the served ViT, counted from the algorithm.

One multiply-add is two operations. The counts follow what each arm
computes, not how the program computes it: the MoE counts each token once,
through the one expert it is routed to (capacity slots left empty are not
work), and a shift or binary matmul counts as many operations as the dense
matmul of its shape. So a count is the same whatever implements the arm.
"""
from __future__ import annotations


def ops_per_image(cfg: dict) -> float:
    """Operations of one image's forward on the configuration's arm."""
    n = (cfg["image_size"] // cfg["patch_size"]) ** 2
    d, f, h = cfg["d_model"], cfg["d_ff"], cfg["n_heads"]
    dh = d // h
    patch_dim = cfg["patch_size"] ** 2 * cfg["in_channels"]
    ops = 2.0 * n * patch_dim * d                 # patch embedding
    per_layer = 4 * 2.0 * n * d * d               # q, k, v, o
    if cfg["policy"] == "dense":
        per_layer += h * 2 * (2.0 * n * n * dh)   # q k^T and p v
    else:
        per_layer += h * 2 * (2.0 * n * dh * dh)  # k^T v and q (k^T v)
        per_layer += 2.0 * 3 * n * d              # V-branch convolution
        per_layer += 2.0 * n * d * len(cfg["moe_experts"])   # router
    per_layer += 2 * 2.0 * n * d * f              # MLP / the routed expert
    ops += cfg["n_layers"] * per_layer
    ops += 2.0 * d * cfg["n_classes"]             # head on the pooled token
    return ops


def shift_matmul_calls(cfg: dict, batch: int) -> list:
    """(m, k, n) of every shift matmul in one shiftadd forward of `batch`
    images: q, k, v, o on all tokens, and the shift expert's two linears on
    its capacity rows."""
    n_tok = (cfg["image_size"] // cfg["patch_size"]) ** 2
    d, f = cfg["d_model"], cfg["d_ff"]
    calls = []
    for _ in range(cfg["n_layers"]):
        calls += [(batch * n_tok, d, d)] * 4
        for kind, cap in zip(cfg["moe_experts"], cfg["moe_capacity_per_image"]):
            if kind == "shift":
                calls += [(batch * cap, d, f), (batch * cap, f, d)]
    return calls


def bidir_attn_calls(cfg: dict, batch: int) -> list:
    """(g, n, dk, dv) of every fused attention call in one forward."""
    n_tok = (cfg["image_size"] // cfg["patch_size"]) ** 2
    dh = cfg["d_model"] // cfg["n_heads"]
    return [(batch * cfg["n_heads"], n_tok, dh, dh)] * cfg["n_layers"]


def kernel_calls(cfg: dict, batch: int) -> dict:
    """Shapes of every call of each kernel in one forward of `batch` images:
    {"shift_matmul": [(m, k, n), ...], "bidir_attn": [(g, n, dk, dv), ...]};
    the dense arm runs neither."""
    if cfg["policy"] == "dense":
        return {"shift_matmul": [], "bidir_attn": []}
    return {"shift_matmul": shift_matmul_calls(cfg, batch),
            "bidir_attn": bidir_attn_calls(cfg, batch)}
