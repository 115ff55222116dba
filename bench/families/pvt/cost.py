"""Operations and kernel calls of the served PVTv2, counted from the
algorithm.

One multiply-add is two operations. The counts follow what each arm
computes, not how the program computes it: the MoE counts each token once,
through the one expert it is routed to (capacity slots left empty are not
work), and a shift or binary matmul counts as many operations as the dense
matmul of its shape. Norms, GELU, softmax and the residual adds are not
counted, as in the vit family's counts. So a count is the same whatever
implements the arm.
"""
from __future__ import annotations


def _stages(cfg: dict):
    """Per stage: (tokens, reduced k/v tokens, width, heads, hidden width,
    sr, kernel, input channels)."""
    side, c_in = cfg["image_size"], cfg["in_channels"]
    for i, c in enumerate(cfg["widths"]):
        k, s = cfg["patch_kernels"][i], cfg["patch_strides"][i]
        side = (side + 2 * (k // 2) - k) // s + 1
        sr = cfg["sr_ratios"][i]
        yield (side * side, (side // sr) ** 2, c, cfg["heads"][i],
               cfg["mlp_ratios"][i] * c, sr, k, c_in)
        c_in = c


def ops_per_image(cfg: dict) -> float:
    """Operations of one image's forward on the configuration's arm."""
    ops = 0.0
    dense = cfg["policy"] == "dense"
    for i, (n, nkv, c, h, f, sr, k, c_in) in enumerate(_stages(cfg)):
        dh = c // h
        ops += 2.0 * n * k * k * c_in * c                # patch embedding
        per_block = 2 * 2.0 * n * c * c                  # q, o
        per_block += 2 * 2.0 * nkv * c * c               # k, v
        if sr > 1:
            per_block += 2.0 * nkv * sr * sr * c * c     # reduction conv
        if dense:
            per_block += h * 2 * (2.0 * n * nkv * dh)    # q k^T and p v
        else:
            per_block += h * 2.0 * (nkv + n) * dh * dh   # k^T v, q (k^T v)
            per_block += 2.0 * 3 * nkv * c               # V-branch conv
            per_block += 2.0 * n * c * len(cfg["moe_experts"])   # router
        per_block += 2 * 2.0 * n * c * f                 # fc1, fc2
        per_block += 2.0 * 9 * n * f                     # Mix-FFN dwconv
        ops += cfg["depths"][i] * per_block
    ops += 2.0 * cfg["widths"][-1] * cfg["n_classes"]   # head
    return ops


def kernel_calls(cfg: dict, batch: int) -> dict:
    """Shapes of every call of each kernel in one forward of `batch` images:
    {"shift_matmul": [(m, k, n), ...], "bidir_attn": [], "bidir_attn_kv":
    [(g, n_q, n_kv, dk, dv), ...]}: q and o on the stage's tokens, k and v
    on its reduced tokens, the shift expert's fc1 and fc2 on its capacity
    rows, and one fused attention call per block of n_q queries against
    n_kv keys. The fused kernel's self-attention list (`bidir_attn`) stays
    empty: no call here has n_q = n_kv but in the last stage, and that one
    is counted with the rest. The dense arm runs neither kernel."""
    calls = {"shift_matmul": [], "bidir_attn": [], "bidir_attn_kv": []}
    if cfg["policy"] == "dense":
        return calls
    for i, (n, nkv, c, h, f, _, _, _) in enumerate(_stages(cfg)):
        caps = dict(zip(cfg["moe_experts"], cfg["moe_capacity_per_image"][i]))
        for _ in range(cfg["depths"][i]):
            calls["shift_matmul"] += [(batch * n, c, c), (batch * nkv, c, c),
                                      (batch * nkv, c, c), (batch * n, c, c)]
            if "shift" in caps:
                cap = caps["shift"]
                calls["shift_matmul"] += [(batch * cap, c, f),
                                          (batch * cap, f, c)]
            calls["bidir_attn_kv"].append((batch * h, n, nkv, c // h, c // h))
    return calls
