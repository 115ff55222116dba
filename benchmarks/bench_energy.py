"""Paper Tab. 3 / Fig. 3: energy comparison, analytic 45 nm model (Tab. 1
unit energies + Horowitz-style data movement — the ShiftAdd-ASIC view).

Reports per-model energy under each policy stage and the attention/MLP
breakdown (the paper's Fig. 3 structure: Add cuts MatMul energy ~94%, Shift
cuts Linear energy ~30-40%, end-to-end 19-43% savings).
"""
from __future__ import annotations

from repro.core import energy
from repro.configs.registry import get_config

# DeiT-T-like ViT (the paper's Tab. 3 row) + two assigned LM archs.
MODELS = {
    "deit_tiny_224": dict(n_layers=12, d_model=192, n_heads=3, d_ff=768,
                          tokens=197),
    "yi-9b@4k": None,
    "rwkv6-3b@4k": None,
}


def _vit_energy(spec, policy):
    L, d, h, f, n = (spec["n_layers"], spec["d_model"], spec["n_heads"],
                     spec["d_ff"], spec["tokens"])
    dh = d // h
    attn_mm = energy.OpEnergy(0, 0)
    attn_lin = energy.OpEnergy(0, 0)
    mlp = energy.OpEnergy(0, 0)
    for _ in range(L):
        # qkvo projections
        lin = (energy.shift_matmul_energy if policy in ("shift_attn", "full")
               else lambda m, k, nn: energy.matmul_energy(m, k, nn, "fp16"))
        for _ in range(4):
            attn_lin += lin(n, d, d)
        # attention contractions per head: (QK)V quadratic or Q(KV) linear+Add
        for _ in range(h):
            if policy in ("la_add", "shift_attn", "full", "moe"):
                attn_mm += energy.add_matmul_energy(dh, n, dh)   # KᵀV
                attn_mm += energy.add_matmul_energy(n, dh, dh)   # Q(KV)
            else:
                attn_mm += energy.matmul_energy(n, dh, n)        # QKᵀ
                attn_mm += energy.matmul_energy(n, n, dh)        # AV
        # MLP
        if policy == "full":
            mlp += energy.shift_matmul_energy(n, d, f)
            mlp += energy.shift_matmul_energy(n, f, d)
        elif policy == "moe":
            # latency-aware split ≈ 2/3 tokens to shift, 1/3 to mult
            mlp += energy.shift_matmul_energy(int(n * 2 / 3), d, f)
            mlp += energy.shift_matmul_energy(int(n * 2 / 3), f, d)
            mlp += energy.matmul_energy(n - int(n * 2 / 3), d, f, "fp16")
            mlp += energy.matmul_energy(n - int(n * 2 / 3), f, d, "fp16")
        else:
            mlp += energy.matmul_energy(n, d, f, "fp16")
            mlp += energy.matmul_energy(n, f, d, "fp16")
    return attn_mm, attn_lin, mlp


def main(rows=None):
    own = rows is None
    rows = [] if own else rows
    spec = MODELS["deit_tiny_224"]
    base = None
    for policy in ("dense", "la_add", "shift_attn", "full", "moe"):
        mm, lin, mlp = _vit_energy(spec, policy)
        total = (mm + lin + mlp).total_pj / 1e9  # mJ
        if base is None:
            base = total
        rows.append((f"energy_deit_t_{policy}", 0.0,
                     f"total_mJ={total:.3f};savings={1 - total / base:+.1%};"
                     f"attn_mJ={(mm + lin).total_pj / 1e9:.3f};"
                     f"mlp_mJ={mlp.total_pj / 1e9:.3f}"))
    # LM archs: per-4k-token forward energy. 1 MAC/param/token; weights read
    # once; dense fp16 (2 B/w) vs shift (shift+add compute, 1 B/w).
    for arch in ("yi-9b", "rwkv6-3b"):
        cfg = get_config(arch)
        toks = 4096
        n_p = cfg.param_count()
        macs = float(toks) * n_p
        dense_c = macs * (energy.MULT_PJ["fp16"] + energy.ADD_PJ["fp16"])
        shift_c = macs * (energy.SHIFT_PJ["int8"] + energy.ADD_PJ["int32"])
        dense_m = energy.DRAM_PJ_PER_BYTE * n_p * 2.0
        shift_m = energy.DRAM_PJ_PER_BYTE * n_p * 1.0
        rows.append((f"energy_{arch}_per4k", 0.0,
                     f"dense_J={(dense_c + dense_m) / 1e12:.2f};"
                     f"shiftadd_J={(shift_c + shift_m) / 1e12:.2f};"
                     f"savings={1 - (shift_c + shift_m) / (dense_c + dense_m):+.1%}"))
    if own:
        for r in rows:
            print(",".join(str(c) for c in r))
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
