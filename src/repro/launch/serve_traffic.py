"""Traffic-serving driver: seeded request traffic through the SLO-aware
micro-batch scheduler and replicated frozen ShiftAddViT engines.

    python -m repro.launch.serve_traffic --scenario poisson --policy shiftadd --replicas 2
    python -m repro.launch.serve_traffic --scenario bursty --policy all --target-p99 400
    python -m repro.launch.serve_traffic --scenario diurnal --arm thread --verify-replay

A seeded trace (`--scenario poisson|bursty|diurnal`, `--requests`, `--seed`)
of variable-size, deadline-classed image requests is pushed through the
fill-or-deadline micro-batch scheduler onto `--replicas` engine replicas
(`--arm thread` on CPU, `--arm sharded` data-parallel on multi-device
backends, `auto` picks). Arrival rate and deadline budgets are calibrated
from the measured per-bucket service times at `--utilization` of replica
capacity, so the default load is feasible by construction and the virtual
timeline is machine-independent up to the calibration. Writes
BENCH_traffic.json (per-policy p50/p95/p99 latency, goodput, deadline-miss
rate, padding waste, dispatch reasons, recompile count) and exits non-zero
if any bucket program recompiled after warmup.
"""
from __future__ import annotations

import argparse
import json
import os

from repro.nn.vit import ViTConfig
from repro.serve.frontend import traffic_sweep
from repro.serve.traffic import SCENARIOS
from repro.serve.vision import SWEEP_POLICIES
from repro.utils.logging import get_logger

log = get_logger("repro.launch.serve_traffic")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="poisson", choices=SCENARIOS)
    ap.add_argument("--policy", default="shiftadd",
                    choices=sorted(SWEEP_POLICIES) + ["all"])
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--arm", default="auto",
                    choices=["auto", "thread", "sharded"])
    ap.add_argument("--requests", type=int, default=500)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--utilization", type=float, default=0.4,
                    help="offered load as a fraction of measured replica "
                         "capacity (the calibrated default load)")
    ap.add_argument("--target-p99", type=float, default=None, metavar="MS",
                    help="SLO target: sets the interactive deadline budget "
                         "(ms) and reports p99 attainment against it")
    ap.add_argument("--slack-frac", type=float, default=0.5,
                    help="deadline-safety dispatch threshold, in units of "
                         "the max-bucket service time")
    ap.add_argument("--linger-frac", type=float, default=1.0,
                    help="padding-tradeoff wait cap (fill-or-deadline "
                         "policy knob), in max-bucket service times")
    ap.add_argument("--max-queue-images", type=int, default=None,
                    help="admission-control bound (default 8 × max bucket)")
    ap.add_argument("--buckets", type=int, nargs="+", default=None,
                    help="override the engine bucket set (default: the "
                         "engine's DEFAULT_BUCKETS; the effective set is "
                         "read back off the engine)")
    ap.add_argument("--image-size", type=int, default=56,
                    help="56 → 196 tokens at patch 4 (DeiT-T-like)")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--impl", choices=["xla", "pallas", "interpret"],
                    default=None)
    ap.add_argument("--tune", default=None, metavar="TUNE_kernels.json",
                    help="persisted autotune table "
                         "(repro.launch.autotune output)")
    ap.add_argument("--no-freeze", action="store_true",
                    help="serve live params instead of the DeployPlan")
    ap.add_argument("--verify-replay", action="store_true",
                    help="serve the trace twice and check routing + logits "
                         "replay bit-identically")
    ap.add_argument("--verify-one-vs-n", action="store_true",
                    help="re-serve the trace on a one-slot pool and check "
                         "per-request logits are bit-identical despite the "
                         "diverging batch compositions (the batch-"
                         "invariance contract, every policy arm)")
    ap.add_argument("--out", default="BENCH_traffic.json")
    args = ap.parse_args(argv)

    # --impl threads explicitly (traffic_sweep → replicas → engines), not
    # via the old process-global ops.set_default_impl override.
    tune = None
    if args.tune:
        from repro.kernels import autotune
        tune = autotune.load_table(args.tune)
        if tune is None:
            log.warning("could not load tune table %s; serving with "
                        "default block caps", args.tune)

    cfg = ViTConfig(image_size=args.image_size, n_layers=args.layers,
                    d_model=args.d_model, d_ff=2 * args.d_model)
    policies = (tuple(sorted(SWEEP_POLICIES)) if args.policy == "all"
                else (args.policy,))
    rec = traffic_sweep(
        cfg, scenario=args.scenario, policies=policies,
        n_requests=args.requests, seed=args.seed, replicas=args.replicas,
        arm=args.arm, utilization=args.utilization, buckets=args.buckets,
        freeze=not args.no_freeze, impl=args.impl, tune=tune,
        slack_frac=args.slack_frac, linger_frac=args.linger_frac,
        max_queue_images=args.max_queue_images,
        target_p99_s=None if args.target_p99 is None
        else args.target_p99 / 1e3,
        verify_replay=args.verify_replay,
        verify_one_vs_n=args.verify_one_vs_n)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)

    recompiled = False
    for name, r in rec["policies"].items():
        lat = r["latency"]
        log.info(
            "%9s [%s x%d]: p50 %6.1f ms  p95 %6.1f ms  p99 %6.1f ms  "
            "goodput %7.1f img/s  miss %.3f  shed %d  waste %.3f  "
            "batches %d (%s)  recompiles %d",
            name, r["arm"], r["replicas"], lat["p50_s"] * 1e3,
            lat["p95_s"] * 1e3, lat["p99_s"] * 1e3,
            r["goodput_images_per_s"], r["deadline_miss_rate"],
            r["shed_requests"], r["padding_waste"], r["batches"],
            ",".join(f"{k}={v}" for k, v in
                     sorted(r["dispatch_reasons"].items())),
            r["recompiles_after_warmup"])
        if "replay_identical_routing" in r:
            log.info("%9s: replay identical routing=%s, bit-identical "
                     "logits=%s", name, r["replay_identical_routing"],
                     r["replay_bit_identical_logits"])
        if "one_vs_n_bit_identical_logits" in r:
            log.info("%9s: 1-vs-N bit-identical logits=%s (batches "
                     "diverged=%s)", name,
                     r["one_vs_n_bit_identical_logits"],
                     r["one_vs_n_diverged_batches"])
        recompiled |= r["recompiles_after_warmup"] > 0
    if rec.get("shiftadd_vs_dense_p99") is not None:
        log.info("shiftadd vs dense p99: %.3fx", rec["shiftadd_vs_dense_p99"])
    log.info("wrote %s", os.path.abspath(args.out))
    if recompiled:
        raise SystemExit("bucket programs recompiled after warmup")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
