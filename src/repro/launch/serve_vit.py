"""Batched ShiftAddViT serving driver — the paper's model behind a
shape-bucketed inference engine.

    python -m repro.launch.serve_vit --policy shiftadd
    python -m repro.launch.serve_vit --policy shiftadd --sweep

Default mode serves a stream of variable-size image requests through
`repro.serve.vision.BucketedViTEngine`: requests are padded into the bucket
batch sizes, every bucket is compiled exactly once at warmup, and steady-state
traffic never retraces (the driver asserts it). --sweep instead runs the same
pretrained dense weights through all conversion stages (dense / stage1 /
shiftadd) and writes BENCH_vit.json with per-policy latency, throughput and
analytic per-image energy.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax

from repro.nn.vit import ShiftAddViT, ViTConfig
from repro.core.policy import DENSE
from repro.serve.vision import (DEFAULT_BUCKETS, BucketedViTEngine,
                                SWEEP_POLICIES, build_policy_model,
                                policy_sweep)
from repro.utils.logging import get_logger

log = get_logger("repro.launch.serve_vit")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="shiftadd",
                    choices=sorted(SWEEP_POLICIES))
    ap.add_argument("--sweep", action="store_true",
                    help="benchmark all policies and write BENCH_vit.json")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--buckets", type=int, nargs="+", default=None,
                    help="override the bucket set (default: the engine's "
                         "DEFAULT_BUCKETS; the effective set is read back "
                         "off the engine and logged)")
    ap.add_argument("--requests", type=int, default=64,
                    help="number of variable-size requests to stream")
    ap.add_argument("--image-size", type=int, default=32)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--impl", choices=["xla", "pallas", "interpret"],
                    default=None,
                    help="force the kernel implementation (CI forces "
                         "interpret to run the Pallas kernel bodies on CPU)")
    ap.add_argument("--tune", default=None, metavar="TUNE_kernels.json",
                    help="persisted autotune table "
                         "(repro.launch.autotune output)")
    ap.add_argument("--no-freeze", action="store_true",
                    help="serve live params instead of the deployment-frozen "
                         "DeployPlan (A/B arm; logits are bit-identical)")
    ap.add_argument("--out", default="BENCH_vit.json")
    args = ap.parse_args()

    # --impl threads explicitly to every engine (policy_sweep and the
    # streaming engine below), not via the old process-global
    # ops.set_default_impl override.
    tune = None
    if args.tune:
        from repro.kernels import autotune
        tune = autotune.load_table(args.tune)
        if tune is None:
            log.warning("could not load tune table %s; serving with "
                        "default block caps", args.tune)

    cfg = ViTConfig(image_size=args.image_size, n_layers=args.layers,
                    d_model=args.d_model, d_ff=2 * args.d_model)

    if args.sweep:
        rec = policy_sweep(cfg, batch=args.batch, buckets=args.buckets,
                           freeze=not args.no_freeze, impl=args.impl,
                           tune=tune)
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=2)
        for name, r in rec["policies"].items():
            log.info("%9s: %7.2f ms/batch %9.1f img/s %8.3f uJ/img "
                     "(recompiles=%d)", name,
                     r["latency_s_per_batch"] * 1e3, r["images_per_s"],
                     r["energy_pj_per_image"] / 1e6,
                     r["recompiles_after_warmup"])
        log.info("wrote %s", os.path.abspath(args.out))
        return

    dense_model = ShiftAddViT(dataclasses.replace(cfg, policy=DENSE))
    dense_params = dense_model.init(jax.random.PRNGKey(0))
    model, params = build_policy_model(cfg, args.policy, dense_model,
                                       dense_params)
    engine = BucketedViTEngine(model, params,
                               buckets=args.buckets or DEFAULT_BUCKETS,
                               freeze=not args.no_freeze,
                               impl=args.impl, tune=tune).warmup()
    traces = engine.trace_count
    log.info("warmup: compiled %d bucket programs %s (frozen=%s%s)", traces,
             list(engine.buckets), engine.frozen,
             f", {engine.plan.frozen_linears} shift weights decoded"
             if engine.plan is not None else "")

    # Stream variable-size requests (sizes cycle over the bucket range).
    sizes = [(i % engine.buckets[-1]) + 1 for i in range(args.requests)]
    shape = (cfg.image_size, cfg.image_size, cfg.in_channels)
    t0 = time.perf_counter()
    n_images = 0
    for i, n in enumerate(sizes):
        imgs = jax.random.normal(jax.random.PRNGKey(100 + i), (n,) + shape)
        jax.block_until_ready(engine.infer(imgs))
        n_images += n
    dt = time.perf_counter() - t0
    if engine.trace_count != traces:
        raise RuntimeError(
            f"bucketed serving retraced after warmup "
            f"({engine.trace_count - traces} extra compiles)")
    log.info("served %d requests (%d images) in %.3fs — %.1f img/s, "
             "0 recompiles after warmup (policy=%s)",
             args.requests, n_images, dt, n_images / dt, args.policy)


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
