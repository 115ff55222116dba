"""Telemetry-trained router driver — extract per-expert serving latencies,
persist them, and fine-tune the MoE router against them.

    python -m repro.launch.tune_router                  # TELEMETRY_experts.json
    python -m repro.launch.tune_router --steps 30 --telemetry TELEMETRY_experts.json
    python -m repro.launch.tune_router --measure off --buckets 1 8 32

Pipeline (ROADMAP item 3, the serving-telemetry → router-training loop):

1. Build the shiftadd policy arm from seeded pretrained-dense weights (the
   same `build_policy_model` conversion every sweep uses — router zero-init,
   all tokens initially on the Mult expert).
2. Extract per-expert telemetry at serving geometry (`serve.telemetry`) —
   or reuse a persisted table via --telemetry (fail-open: absent/invalid
   falls back to extraction) — and save it to --out.
3. Apply the α latencies to the MoE feeds and fine-tune ONLY the router
   (`train.router_tune`, gradient-masked AdamW on the balance loss).
4. Report before/after loss and the frozen-engine expert token share (the
   PR-3 deploy freeze serves the eval), so the paper's claim — faster
   experts win more tokens — is visible in the log.

The persisted table feeds `--telemetry` on bench_traffic.py, whose router
arm re-runs steps 3-4 inside the virtual-clock sweep and is gated by
check_traffic.py (router p99 <= analytic-shiftadd p99, shift share up).
"""
from __future__ import annotations

import argparse
import dataclasses
import os

import jax

from repro.utils.logging import get_logger

log = get_logger("repro.launch.tune_router")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--image-size", type=int, default=56,
                    help="56 → 196 tokens at patch 4 (DeiT-T-like, the "
                         "serving-benchmark geometry)")
    ap.add_argument("--patch-size", type=int, default=4)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--d-ff", type=int, default=None,
                    help="default 2 × d_model (the benchmark convention)")
    ap.add_argument("--buckets", type=int, nargs="+", default=None,
                    help="serving bucket set to probe (default: the "
                         "engine's DEFAULT_BUCKETS)")
    ap.add_argument("--iters", type=int, default=5,
                    help="timed probe rounds per expert × bucket")
    ap.add_argument("--measure", choices=["auto", "on", "off"],
                    default="auto",
                    help="wall-clock α (auto: only on a TPU backend; "
                         "elsewhere the analytic model at serving geometry "
                         "decides and the table records why)")
    ap.add_argument("--steps", type=int, default=40,
                    help="router fine-tune steps")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--batch", type=int, default=16,
                    help="fine-tune/eval image batch")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry", default=None,
                    help="existing TELEMETRY_experts.json to reuse instead "
                         "of probing (fail-open: falls back to extraction)")
    ap.add_argument("--tune", default=None,
                    help="TUNE_kernels.json to thread through the frozen "
                         "probes (fail-open)")
    ap.add_argument("--out", default="TELEMETRY_experts.json")
    args = ap.parse_args(argv)

    from repro.core.policy import DENSE
    from repro.kernels.autotune import load_table
    from repro.nn.vit import ShiftAddViT, ViTConfig
    from repro.serve import telemetry as tm
    from repro.serve.vision import build_policy_model
    from repro.train.router_tune import finetune_report, router_finetune

    tune = None
    if args.tune:
        tune = load_table(args.tune)
        if tune is None:
            log.warning("tune table %s missing/invalid — default blocks",
                        args.tune)

    base_cfg = ViTConfig(image_size=args.image_size,
                         patch_size=args.patch_size, n_layers=args.layers,
                         d_model=args.d_model, n_heads=args.heads,
                         d_ff=args.d_ff or 2 * args.d_model)
    dense_model = ShiftAddViT(dataclasses.replace(base_cfg, policy=DENSE))
    dense_params = dense_model.init(jax.random.PRNGKey(args.seed))
    model, params = build_policy_model(base_cfg, "shiftadd", dense_model,
                                       dense_params)

    telem = tm.load_telemetry(args.telemetry) if args.telemetry else None
    if telem is not None:
        log.info("reusing telemetry %s (mode=%s)", args.telemetry,
                 telem.mode)
    else:
        if args.telemetry:
            log.warning("telemetry %s missing/invalid — extracting fresh",
                        args.telemetry)
        measure = {"auto": None, "on": True, "off": False}[args.measure]
        telem = tm.extract_expert_telemetry(
            model, params, buckets=args.buckets, tune=tune,
            iters=args.iters, measure=measure)
    telem.save(args.out)

    meta = telem.meta_dict
    kinds = tuple(meta.get("expert_kinds", ("mult", "shift")))
    log.info("telemetry mode=%s backend=%s (%s)", meta.get("mode"),
             meta.get("backend"), meta.get("reason"))
    for kind in kinds:
        log.info("  %-6s alpha_lat=%.3e s  buckets=%s", kind,
                 dict(telem.alpha_latencies)[kind],
                 {b: f"{s:.2e}" for b, s in telem.bucket_seconds(kind).items()})

    shape = (base_cfg.image_size, base_cfg.image_size, base_cfg.in_channels)
    imgs = jax.random.normal(jax.random.PRNGKey(args.seed + 1),
                             (args.batch,) + shape)
    tm.apply_expert_latencies(model, telem)
    before = finetune_report(model, params, imgs, tune=tune)
    tuned, history = router_finetune(model, params, imgs, steps=args.steps,
                                     lr=args.lr)
    after = finetune_report(model, tuned, imgs, tune=tune)

    log.info("router fine-tune: %d steps, balance loss %.4f → %.4f",
             len(history), history[0], history[-1])
    log.info("expert token share (frozen-engine eval): %s → %s  caps=%s",
             before["expert_token_share"], after["expert_token_share"],
             after["capacities_per_image"])
    log.info("wrote %s", os.path.abspath(args.out))


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
