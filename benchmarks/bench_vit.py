"""ShiftAddViT policy-sweep serving benchmark. Writes BENCH_vit.json so the
paper's headline claim (latency + energy reduction vs the dense ViT) has a
per-PR trajectory, next to BENCH_serve.json's LM numbers.

    PYTHONPATH=src python benchmarks/bench_vit.py [--batch 32]
    PYTHONPATH=src python benchmarks/bench_vit.py --no-freeze   # A/B arm
    PYTHONPATH=src python benchmarks/bench_vit.py --impl interpret
    PYTHONPATH=src python benchmarks/bench_vit.py --tune TUNE_kernels.json

The record also carries a nested `pallas_arm`: a shiftadd-only sweep at
impl=pallas (real kernels on TPU, interpret-mode smoke at reduced geometry
elsewhere) next to an impl=xla twin at the same geometry, fed through the
persisted autotune table when `--tune` is given. check_vit_pallas.py gates
`pallas <= xla` per bucket on it (skip-with-reason off-TPU).

One set of pretrained dense weights is pushed through `convert_from` at
stage 0 (dense), stage 1 (binary-linear attention) and stage 2 (+ MoE of
Mult/Shift primitives), then served through the shape-bucketed inference
engine with the deployment freeze on (default) or off (`--no-freeze`).
Default geometry is DeiT-T-like: 196 tokens (56×56 image, patch 4) — the
sequence length the paper's serving claim is made at; `--image-size 32`
reproduces the old toy scale.

Reported per policy: batch latency (median), throughput, analytic per-image
energy (paper Tab. 1 unit energies + DRAM movement), the engine's compile
counts (recompiles_after_warmup must be 0 — gated in CI), the freeze state,
and the latency ratio vs the dense arm (`shiftadd_vs_dense_latency` is the
paper's crossover, gated ≤ 1.0 in the acceptance criteria). Device time
per model component comes from a device trace read by the model's named
scopes (bench/run.py --trace 1), not from this host-clock sweep.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.nn.vit import ViTConfig
from repro.serve.vision import policy_sweep

# Reduced geometry for the CPU interpret-mode smoke of the pallas arm: the
# whole tuned-kernel path (table → DeployPlan → frozen engine → pallas_call
# under the interpreter) at a size where interpreting every kernel stays
# cheap. Timings from this geometry are NOT kernel timings.
SMOKE_CFG = dict(image_size=16, patch_size=4, n_layers=2, d_model=32,
                 n_heads=2, d_ff=64)
SMOKE_BATCH, SMOKE_ITERS, SMOKE_BUCKETS = 4, 3, (1, 4)


def pallas_arm(cfg=None, batch=32, iters=10, tune=None):
    """The measured impl=pallas serving arm (nested under "pallas_arm" in
    BENCH_vit.json) plus an impl=xla twin sweep at the SAME geometry — the
    per-bucket pair check_vit_pallas.py gates `pallas <= xla` on.

    mode "tpu": real Pallas kernels at the benchmark geometry, through the
    persisted autotune table when one is given.
    mode "interpret-smoke" (any non-TPU backend): interpreter-executed
    kernels at SMOKE_CFG geometry — proves the serving path end to end, but
    the latency gate must be skipped (check_vit_pallas.py prints the
    carried skip_reason and exits 0).
    """
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        mode, kernel_impl, skip_reason = "tpu", "pallas", None
        arm_cfg = cfg or ViTConfig(image_size=56)
        arm_batch, arm_iters, arm_buckets = batch, max(iters, 10), None
    else:
        mode, kernel_impl = "interpret-smoke", "interpret"
        skip_reason = (f"backend={backend}: Pallas kernels ran under the "
                       "interpreter at reduced geometry; timings are "
                       "interpreter overhead, not kernel performance")
        arm_cfg = ViTConfig(**SMOKE_CFG)
        arm_batch, arm_iters, arm_buckets = (SMOKE_BATCH, SMOKE_ITERS,
                                             SMOKE_BUCKETS)
    kw = dict(batch=arm_batch, iters=arm_iters, buckets=arm_buckets,
              policies=("shiftadd",), freeze=True)
    rec_pallas = policy_sweep(arm_cfg, impl=kernel_impl, tune=tune, **kw)
    rec_xla = policy_sweep(arm_cfg, impl="xla", tune=None, **kw)
    return {
        "mode": mode,
        "backend": backend,
        "impl": kernel_impl,
        "tuned": tune is not None,
        "skip_reason": skip_reason,
        "geometry": {"image_size": arm_cfg.image_size,
                     "n_layers": arm_cfg.n_layers,
                     "d_model": arm_cfg.d_model,
                     "batch": arm_batch, "iters": arm_iters,
                     "buckets": rec_pallas.get("buckets")},
        "pallas": rec_pallas,
        "xla": rec_xla,
    }


def main(rows=None):
    if rows is not None:
        # benchmarks/run.py harness mode: tiny geometry, CSV row contract.
        from repro.nn.vit import ViTConfig as _Cfg
        rec = policy_sweep(_Cfg(image_size=16, patch_size=4, n_layers=2,
                                d_model=32, n_heads=2, d_ff=64),
                           batch=8, iters=2, buckets=(8,))
        for name, r in rec["policies"].items():
            rows.append((f"vit_serve_{name}", r["latency_s_per_batch"] * 1e6,
                         f"img_s={r['images_per_s']:.1f}"))
        return

    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--image-size", type=int, default=56,
                    help="56 → 196 tokens at patch 4 (DeiT-T-like)")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--impl", choices=["xla", "pallas", "interpret"],
                    default=None,
                    help="force the kernel implementation (CI uses this to "
                         "exercise the interpret path)")
    ap.add_argument("--tune", default=None, metavar="TUNE_kernels.json",
                    help="persisted autotune table (launch/autotune.py "
                         "output); tuned block caps feed every pallas/"
                         "interpret kernel call, the pallas_arm included")
    ap.add_argument("--skip-pallas-arm", action="store_true",
                    help="omit the nested impl=pallas arm (it adds two "
                         "extra sweeps)")
    ap.add_argument("--no-freeze", action="store_true",
                    help="serve the live params instead of the DeployPlan "
                         "(the A/B arm of the freeze benchmark)")
    ap.add_argument("--ab-freeze", action="store_true",
                    help="run the interleaved frozen-vs-live A/B of the "
                         "shiftadd arm instead of the policy sweep (the CI "
                         "freeze gate's measurement; noise-robust)")
    ap.add_argument("--out", default=None,
                    help="output path (default: BENCH_vit.json, or "
                         "BENCH_vit_freeze_ab.json under --ab-freeze)")
    args = ap.parse_args()
    if args.out is None:
        name = "BENCH_vit_freeze_ab.json" if args.ab_freeze else "BENCH_vit.json"
        args.out = os.path.join(os.path.dirname(__file__), "..", name)

    # NOTE: --impl threads explicitly through policy_sweep → engine → kernel
    # ops (never via ops.set_default_impl — the old process-global override
    # leaked into every later engine in the process; satellite bugfix).
    tune = None
    if args.tune:
        from repro.kernels import autotune
        tune = autotune.load_table(args.tune)
        if tune is None:
            print(f"WARNING: could not load tune table {args.tune}; "
                  f"serving with default block caps")

    cfg = ViTConfig(image_size=args.image_size, n_layers=args.layers,
                    d_model=args.d_model, d_ff=2 * args.d_model)
    if args.ab_freeze:
        from repro.serve.vision import freeze_ab
        rec = freeze_ab(cfg, batch=args.batch, iters=max(args.iters, 15))
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=2)
        print(f"freeze A/B ({rec['policy']}): frozen "
              f"{rec['frozen_latency_s'] * 1e3:.2f} ms vs live "
              f"{rec['live_latency_s'] * 1e3:.2f} ms "
              f"({rec['frozen_vs_live']:.3f}x, interleaved, "
              f"recompiles={rec['recompiles_after_warmup']})")
        print(f"wrote {os.path.abspath(args.out)}")
        return
    rec = policy_sweep(cfg, batch=args.batch, iters=args.iters,
                       freeze=not args.no_freeze, impl=args.impl,
                       tune=tune)
    if not args.skip_pallas_arm:
        rec["pallas_arm"] = pallas_arm(cfg, batch=args.batch,
                                       iters=args.iters, tune=tune)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)

    dense = rec["policies"]["dense"]
    for name, r in rec["policies"].items():
        lat = r["latency"]
        print(f"{name:>9}: {r['latency_s_per_batch'] * 1e3:8.2f} ms/batch  "
              f"p50/p95/p99 {lat['p50_s'] * 1e3:.2f}/{lat['p95_s'] * 1e3:.2f}"
              f"/{lat['p99_s'] * 1e3:.2f} ms  "
              f"{r['images_per_s']:9.1f} img/s  "
              f"{r['energy_pj_per_image'] / 1e6:8.3f} uJ/img  "
              f"({r['latency_vs_dense']:.2f}x dense latency, "
              f"{r['energy_pj_per_image'] / dense['energy_pj_per_image']:.2f}x "
              f"dense energy, frozen={r['frozen']}, buckets={r['buckets']}, "
              f"waste={r['padding_waste']:.3f}, "
              f"recompiles={r['recompiles_after_warmup']})")
    if "shiftadd_vs_dense_latency" in rec:
        print(f"shiftadd vs dense latency: "
              f"{rec['shiftadd_vs_dense_latency']:.3f}x (frozen={rec['frozen']})")
    if "pallas_arm" in rec:
        arm = rec["pallas_arm"]
        p = arm["pallas"]["policies"]["shiftadd"]["latency"]
        x = arm["xla"]["policies"]["shiftadd"]["latency"]
        print(f"pallas arm [{arm['mode']}]: pallas p50 "
              f"{p['p50_s'] * 1e3:.2f} ms vs xla p50 "
              f"{x['p50_s'] * 1e3:.2f} ms (tuned={arm['tuned']})")
    print(f"wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
