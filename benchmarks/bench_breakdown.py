"""Paper Tab. 4/6: component-wise breakdown of ShiftAddViT variants.

Per variant (MSA → +LinearAttn → +Add(Quant) → +Shift → +MoE) reports the
v5e roofline-model latency of one DeiT-T-like forward (batch 32) plus the
45 nm analytic energy — the two axes of the paper's breakdown tables.
"""
from __future__ import annotations

from repro.core import energy
from repro.core.energy import HBM_BW, PEAK_FLOPS_BF16, PEAK_OPS_INT8

SPEC = dict(n_layers=12, d_model=192, n_heads=3, d_ff=768, tokens=197,
            batch=32)


def _lin_time(m, k, n, kind):
    flops = 2.0 * m * k * n
    if kind == "dense":
        return max(flops / PEAK_FLOPS_BF16, (m * k + k * n + m * n) * 2 / HBM_BW)
    # shift / add: int8 second operand, int8 MXU rate
    return max(flops / PEAK_OPS_INT8, (m * k * 2 + k * n + m * n * 2) / HBM_BW)


def variant_time(attn, proj, mlp):
    s = SPEC
    b, L, d, h, f, n = (s["batch"], s["n_layers"], s["d_model"], s["n_heads"],
                        s["d_ff"], s["tokens"])
    dh = d // h
    t = 0.0
    e = energy.OpEnergy(0, 0)
    for _ in range(L):
        for _ in range(4):
            t += _lin_time(b * n, d, d, proj)
            e += (energy.shift_matmul_energy(b * n, d, d) if proj == "shift"
                  else energy.matmul_energy(b * n, d, d, "fp16"))
        if attn == "msa":
            t += _lin_time(b * h * n, dh, n, "dense")
            t += _lin_time(b * h * n, n, dh, "dense")
            e += energy.matmul_energy(b * h * n, dh, n)
            e += energy.matmul_energy(b * h * n, n, dh)
        else:  # linear order Q(KV); "add" binarizes the contractions
            kind = "add" if attn == "add" else "dense"
            t += _lin_time(b * h * dh, n, dh, kind)
            t += _lin_time(b * h * n, dh, dh, kind)
            fn = (energy.add_matmul_energy if attn == "add"
                  else lambda m, k, nn: energy.matmul_energy(m, k, nn, "fp16"))
            e += fn(b * h * dh, n, dh)
            e += fn(b * h * n, dh, dh)
        if mlp == "moe":
            t_shift = (_lin_time(int(b * n * 2 / 3), d, f, "shift")
                       + _lin_time(int(b * n * 2 / 3), f, d, "shift"))
            t_mult = (_lin_time(b * n - int(b * n * 2 / 3), d, f, "dense")
                      + _lin_time(b * n - int(b * n * 2 / 3), f, d, "dense"))
            t += max(t_shift, t_mult)       # parallel experts: max finish
            e += energy.shift_matmul_energy(int(b * n * 2 / 3), d, f)
            e += energy.shift_matmul_energy(int(b * n * 2 / 3), f, d)
            e += energy.matmul_energy(b * n - int(b * n * 2 / 3), d, f, "fp16")
            e += energy.matmul_energy(b * n - int(b * n * 2 / 3), f, d, "fp16")
        else:
            kind = "shift" if mlp == "shift" else "dense"
            t += _lin_time(b * n, d, f, kind)
            t += _lin_time(b * n, f, d, kind)
            fn = (energy.shift_matmul_energy if mlp == "shift"
                  else lambda m, k, nn: energy.matmul_energy(m, k, nn, "fp16"))
            e += fn(b * n, d, f)
            e += fn(b * n, f, d)
    return t, e.total_pj / 1e9


VARIANTS = [
    ("msa", ("msa", "dense", "dense")),
    ("linear_attn", ("linear", "dense", "dense")),
    ("la_add_quant", ("add", "dense", "dense")),
    ("la_add_shiftattn", ("add", "shift", "dense")),
    ("la_add_shift_both", ("add", "shift", "shift")),
    ("la_add_moe_both", ("add", "shift", "moe")),
]


def main(rows=None):
    own = rows is None
    rows = [] if own else rows
    base_t = base_e = None
    for name, (attn, proj, mlp) in VARIANTS:
        t, e = variant_time(attn, proj, mlp)
        if base_t is None:
            base_t, base_e = t, e
        rows.append((f"breakdown_{name}", t * 1e6,
                     f"latency_vs_msa={base_t / t:.2f}x;energy_mJ={e:.2f};"
                     f"energy_savings={1 - e / base_e:+.1%}"))
    if own:
        for r in rows:
            print(",".join(str(c) for c in r))
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
