"""Elastic-serving driver: diurnal traffic through the autoscaling /
failure-injection / graceful-degradation control plane (serve.elastic).

    python -m repro.launch.serve_elastic --scenario diurnal --requests 220
    python -m repro.launch.serve_elastic --max-replicas 3 --spares 2
    python -m repro.launch.serve_elastic --no-faults --utilization 1.3

Runs the two-arm comparison elastic_sweep defines: a FIXED min-replica
baseline (which must saturate at the diurnal peak) against the elastic
control plane (warm-pool autoscaling between --min-replicas and
--max-replicas, a replica kill at --kill-at and a straggler slowdown at
--slowdown-at of the virtual horizon, dense→shiftadd degradation per
deadline class when the pool saturates). Writes BENCH_elastic.json and
exits non-zero if the elastic arm missed a deadline, anything recompiled
after warmup, or the seeded replay diverged — the same conditions
benchmarks/check_elastic.py gates in CI.
"""
from __future__ import annotations

import argparse
import json
import os

from repro.nn.vit import ViTConfig
from repro.serve.elastic import elastic_sweep
from repro.serve.traffic import SCENARIOS
from repro.utils.logging import get_logger

log = get_logger("repro.launch.serve_elastic")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="diurnal", choices=SCENARIOS)
    ap.add_argument("--requests", type=int, default=220)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--min-replicas", type=int, default=1)
    ap.add_argument("--max-replicas", type=int, default=2)
    ap.add_argument("--spares", type=int, default=2,
                    help="pre-warmed engines beyond max-replicas (failure "
                         "headroom; all compiled at warmup)")
    ap.add_argument("--arm", default="thread", choices=["thread", "sharded"],
                    help="sharded pins each reserve engine to its own "
                         "device (needs >= max-replicas + spares devices)")
    ap.add_argument("--utilization", type=float, default=1.15,
                    help="offered load / min-replica capacity; > 1 so the "
                         "fixed baseline misses at the peak")
    ap.add_argument("--kill-at", type=float, default=0.35, metavar="FRAC")
    ap.add_argument("--slowdown-at", type=float, default=0.6, metavar="FRAC")
    ap.add_argument("--slowdown-factor", type=float, default=4.0)
    ap.add_argument("--no-faults", action="store_true")
    ap.add_argument("--image-size", type=int, default=56)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--impl", choices=["xla", "pallas", "interpret"],
                    default=None)
    ap.add_argument("--tune", default=None, metavar="TUNE_kernels.json")
    ap.add_argument("--no-verify-replay", action="store_true")
    ap.add_argument("--out", default="BENCH_elastic.json")
    args = ap.parse_args(argv)

    tune = None
    if args.tune:
        from repro.kernels import autotune
        tune = autotune.load_table(args.tune)
        if tune is None:
            log.warning("could not load tune table %s; serving with "
                        "default block caps", args.tune)

    cfg = ViTConfig(image_size=args.image_size, n_layers=args.layers,
                    d_model=args.d_model, d_ff=2 * args.d_model)
    rec = elastic_sweep(
        cfg, scenario=args.scenario, n_requests=args.requests,
        seed=args.seed, min_replicas=args.min_replicas,
        max_replicas=args.max_replicas, spares=args.spares, arm=args.arm,
        utilization=args.utilization, impl=args.impl, tune=tune,
        kill_at_frac=None if args.no_faults else args.kill_at,
        slowdown_at_frac=None if args.no_faults else args.slowdown_at,
        slowdown_factor=args.slowdown_factor,
        verify_replay=not args.no_verify_replay)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)

    for arm in ("baseline", "elastic"):
        r = rec[arm]
        log.info("%9s: p50 %6.1f ms  p99 %6.1f ms  miss %.3f  shed %d  "
                 "recompiles %d", arm, r["latency"]["p50_s"] * 1e3,
                 r["latency"]["p99_s"] * 1e3, r["deadline_miss_rate"],
                 r["shed_requests"], r["recompiles_after_warmup"])
    e = rec["elastic"]
    log.info("elastic: ups %d downs %d kills %d evictions %d recoveries %d "
             "degraded %d max_active %d replica_s %.1f",
             e["scale_ups"], e["scale_downs"], e["kills"],
             e["straggler_evictions"], e["recoveries"],
             e["degraded_requests"], e["max_active"], e["replica_seconds"])
    if "replay_identical_events" in rec:
        log.info("replay: events=%s logits=%s",
                 rec["replay_identical_events"],
                 rec["replay_bit_identical_logits"])
    log.info("wrote %s", os.path.abspath(args.out))

    bad = []
    if e["deadline_miss_rate"] > 0:
        bad.append("elastic arm missed deadlines")
    if rec["recompiles_after_warmup"] > 0:
        bad.append("programs recompiled after warmup")
    if not rec.get("replay_identical_events", True) \
            or not rec.get("replay_bit_identical_logits", True):
        bad.append("seeded replay diverged")
    if bad:
        raise SystemExit("; ".join(bad))


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
