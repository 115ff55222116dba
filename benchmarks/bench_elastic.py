"""Elastic-serving benchmark: the diurnal autoscaling + failure-injection +
graceful-degradation scenario. Writes BENCH_elastic.json.

    PYTHONPATH=src python benchmarks/bench_elastic.py [--requests 220]
    PYTHONPATH=src python benchmarks/bench_elastic.py --no-faults

One seeded diurnal trace, deliberately calibrated ABOVE a fixed
min-replica pool: offered load is --utilization (> 1) of the min-replica
capacity and the sinusoidal peak multiplies it by RAMP_HI on top. Two arms
share the same warmed pools, trace, and virtual clock:

- baseline: a FIXED pool of min-replicas (no autoscaler, no degradation,
  no faults) — it must MISS deadlines at the peak (recorded miss rate > 0,
  or the scenario proves nothing).
- elastic: the control plane (serve.elastic) scales between min and max
  replicas from the warm pool, sheds saturated-pool load to the shiftadd
  degrade arm per deadline class, and survives an injected replica kill
  plus an injected straggler (slowdown → monitor eviction → warm-pool
  backfill) at chosen virtual times — with ZERO deadline misses and ZERO
  recompiles (the warm-pool trace_count invariant spans every scale and
  recovery event).

A replay from a reset control plane must reproduce the full elastic
signature (routing incl. arm, scale timeline, fault firings, degradation
decisions) and every logit bit for bit. benchmarks/check_elastic.py gates
all of it, and additionally that the scenario actually exercised the
machinery (scale-ups happened, the kill fired, requests degraded).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.nn.vit import ViTConfig
from repro.serve.elastic import elastic_sweep
from repro.serve.traffic import SCENARIOS


def run(scenario="diurnal", requests=220, seed=0, min_replicas=1,
        max_replicas=2, spares=2, utilization=1.15, image_size=56, layers=4,
        d_model=128, impl=None, tune=None, kill_at_frac=0.35,
        slowdown_at_frac=0.6, slowdown_factor=4.0, verify_replay=True,
        buckets=None):
    cfg = ViTConfig(image_size=image_size, n_layers=layers, d_model=d_model,
                    d_ff=2 * d_model)
    return elastic_sweep(
        cfg, scenario=scenario, n_requests=requests, seed=seed,
        min_replicas=min_replicas, max_replicas=max_replicas, spares=spares,
        utilization=utilization, impl=impl, tune=tune, buckets=buckets,
        kill_at_frac=kill_at_frac, slowdown_at_frac=slowdown_at_frac,
        slowdown_factor=slowdown_factor, verify_replay=verify_replay)


def main(rows=None):
    if rows is not None:
        # benchmarks/run.py harness mode: tiny geometry, CSV row contract.
        rec = run(requests=60, image_size=16, layers=2, d_model=32,
                  buckets=(1, 2, 4), verify_replay=False)
        for arm in ("baseline", "elastic"):
            r = rec[arm]
            rows.append((f"elastic_{arm}_p99", r["latency"]["p99_s"] * 1e6,
                         f"miss={r['deadline_miss_rate']:.3f}"))
        rows.append(("elastic_replica_seconds",
                     rec["elastic"]["replica_seconds"] * 1e6,
                     f"max_active={rec['elastic']['max_active']}"))
        return

    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="diurnal", choices=SCENARIOS)
    ap.add_argument("--requests", type=int, default=220)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--min-replicas", type=int, default=1)
    ap.add_argument("--max-replicas", type=int, default=2)
    ap.add_argument("--spares", type=int, default=2,
                    help="extra pre-warmed engines beyond max-replicas "
                         "(failure-recovery headroom; every spare is "
                         "compiled at warmup, attach never traces)")
    ap.add_argument("--utilization", type=float, default=1.15,
                    help="offered load as a fraction of the MIN-replica "
                         "capacity — above 1 so the fixed baseline "
                         "saturates at the diurnal peak")
    ap.add_argument("--image-size", type=int, default=56)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--impl", choices=["xla", "pallas", "interpret"],
                    default=None)
    ap.add_argument("--tune", default=None, metavar="TUNE_kernels.json")
    ap.add_argument("--kill-at", type=float, default=0.35, metavar="FRAC",
                    help="inject a replica kill at this fraction of the "
                         "trace horizon (virtual time)")
    ap.add_argument("--slowdown-at", type=float, default=0.6, metavar="FRAC",
                    help="inject a straggler (service-time multiplier) at "
                         "this fraction of the horizon")
    ap.add_argument("--slowdown-factor", type=float, default=4.0)
    ap.add_argument("--no-faults", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.out is None:
        args.out = os.path.join(os.path.dirname(__file__), "..",
                                "BENCH_elastic.json")
    tune = None
    if args.tune:
        from repro.kernels import autotune
        tune = autotune.load_table(args.tune)
        if tune is None:
            print(f"WARNING: could not load tune table {args.tune}; "
                  f"serving with default block caps")

    rec = run(scenario=args.scenario, requests=args.requests, seed=args.seed,
              min_replicas=args.min_replicas, max_replicas=args.max_replicas,
              spares=args.spares, utilization=args.utilization,
              image_size=args.image_size, layers=args.layers,
              d_model=args.d_model, impl=args.impl, tune=tune,
              kill_at_frac=None if args.no_faults else args.kill_at,
              slowdown_at_frac=None if args.no_faults else args.slowdown_at,
              slowdown_factor=args.slowdown_factor)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)
    for arm in ("baseline", "elastic"):
        r = rec[arm]
        lat = r["latency"]
        print(f"{arm:>9}: p50 {lat['p50_s'] * 1e3:7.1f} ms  "
              f"p99 {lat['p99_s'] * 1e3:7.1f} ms  "
              f"miss {r['deadline_miss_rate']:.3f}  "
              f"shed {r['shed_requests']}  "
              f"recompiles {r['recompiles_after_warmup']}")
    e = rec["elastic"]
    print(f"  elastic: ups {e['scale_ups']}  downs {e['scale_downs']}  "
          f"kills {e['kills']}  straggler_evictions "
          f"{e['straggler_evictions']}  recoveries {e['recoveries']}  "
          f"degraded {e['degraded_requests']} {e['degraded_by_class']}  "
          f"max_active {e['max_active']}  "
          f"replica_s {e['replica_seconds']:.1f}")
    if "replay_identical_events" in rec:
        print(f"  replay: events={rec['replay_identical_events']} "
              f"logits={rec['replay_bit_identical_logits']}")
    print(f"wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
