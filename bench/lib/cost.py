"""Operations and bytes of the served ViT, counted from the algorithm.

One multiply-add is two operations. The counts follow what each arm
computes, not how the program computes it: the MoE counts each token once,
through the one expert it is routed to (capacity slots left empty are not
work), and a shift or binary matmul counts as many operations as the dense
matmul of its shape. So a count is the same whatever implements the arm.
"""
from __future__ import annotations


def vit_ops_per_image(cfg: dict) -> float:
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


def shift_matmul_cost(m: int, k: int, n: int) -> tuple:
    """(ops, bytes) of y (m, n) f32 = x (m, k) f32 @ w (k, n), w one int8
    byte per weight: every operand read or written once."""
    return 2.0 * m * k * n, 4.0 * m * k + 1.0 * k * n + 4.0 * m * n


def bidir_attn_cost(g: int, n: int, dk: int, dv: int) -> tuple:
    """(ops, bytes) of fused binary linear attention over g (batch x head)
    groups: k^T v and q (k^T v) at two operations a term, the row sums and
    the normaliser; q, k, v read and the output written once, in f32."""
    ops = g * (2.0 * n * dk * dv * 2 + 2.0 * n * dk + 2.0 * n * dv)
    return ops, 4.0 * g * n * (2 * dk + 2 * dv)


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


def roofline_s(ops: float, nbytes: float, peak_ops: float,
               peak_bytes_per_s: float) -> float:
    """Least time the chip could take: the larger of the compute bound and
    the memory bound."""
    return max(ops / peak_ops, nbytes / peak_bytes_per_s)
