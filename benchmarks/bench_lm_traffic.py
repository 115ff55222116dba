"""LM traffic-serving benchmark: token-level continuous batching vs the
static fixed-batch refill baseline on one seeded mixed-length trace.
Writes BENCH_lm_traffic.json — the LM decode twin of BENCH_traffic.json,
sharing its latency-summary schema (serve.metrics).

    PYTHONPATH=src python benchmarks/bench_lm_traffic.py [--requests 60]
    PYTHONPATH=src python benchmarks/bench_lm_traffic.py --scenario bursty

Both modes run on the SAME warmed `BucketedLMEngine` pool — "static" is a
host-side gang-refill admission policy, not a different engine — so the
tokens/s comparison carries zero compile-count confounds. The default load
is an overload (utilization 1.5× the calibrated full-occupancy request
capacity): continuous admission keeps decode slots busy where gang refill
drains them, which is the structural win the CI gate
(benchmarks/check_lm_traffic.py) asserts as continuous >= static tokens/s,
alongside zero recompiles after warmup, prefill program count == engines ×
prompt buckets, bit-identical seeded replay (dispatch, tokens, logits), and
per-request logits bit-identical to a batch=1 serial oracle on the same
engine (`one_vs_n_bit_identical_logits` — the MoE shiftadd arm included,
served at the generous no-drop capacity).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.serve.frontend import lm_traffic_sweep
from repro.serve.traffic import SCENARIOS


def run(scenario="poisson", requests=60, seed=0, replicas=1, slots=4,
        buckets=(4, 8, 16), chunk=4, layers=2, d_model=64, vocab=256,
        utilization=1.5, verify=True):
    return lm_traffic_sweep(
        scenario=scenario, policies=("stage1", "shiftadd"),
        n_requests=requests, seed=seed, n_replicas=replicas, n_slots=slots,
        prompt_buckets=tuple(buckets), chunk=chunk, layers=layers,
        d_model=d_model, vocab_size=vocab, utilization=utilization,
        verify_replay=verify, verify_serial_oracle=verify)


def _print_record(rec):
    for name, r in rec["policies"].items():
        c, s = r["continuous"], r["static"]
        print(f"{name:>9}: continuous {c['tokens_per_s']:8.1f} tok/s "
              f"(occ {c['chunk_occupancy']:.2f})  static "
              f"{s['tokens_per_s']:8.1f} tok/s (occ "
              f"{s['chunk_occupancy']:.2f})  ratio "
              f"{r['continuous_vs_static_tokens_per_s']:.3f}x  "
              f"ttft p50 {c['ttft']['p50_s'] * 1e3:.1f} ms  "
              f"recompiles {c['recompiles_after_warmup']}"
              f"/{s['recompiles_after_warmup']}")
        if "one_vs_n_bit_identical_logits" in r:
            print(f"{'':>9}  verify [replay={r['replay_bit_identical_logits']}"
                  f" 1vsN={r['one_vs_n_bit_identical_logits']}"
                  f" compared={r['one_vs_n_compared']}]")


def main(rows=None):
    if rows is not None:
        # benchmarks/run.py harness mode: tiny geometry, CSV row contract.
        rec = run(requests=16, slots=2, buckets=(4, 8), layers=2, d_model=32,
                  vocab=64, verify=False)
        for name, r in rec["policies"].items():
            c = r["continuous"]
            rows.append((f"lm_traffic_{name}_ttft_p50",
                         c["ttft"]["p50_s"] * 1e6,
                         f"cont_vs_static="
                         f"{r['continuous_vs_static_tokens_per_s']:.2f}x"))
        return

    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="poisson", choices=SCENARIOS)
    ap.add_argument("--requests", type=int, default=60)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--buckets", type=int, nargs="+", default=[4, 8, 16])
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--utilization", type=float, default=1.5)
    ap.add_argument("--skip-verify", action="store_true",
                    help="omit the replay + batch=1 oracle verification")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.out is None:
        args.out = os.path.join(os.path.dirname(__file__), "..",
                                "BENCH_lm_traffic.json")

    rec = run(scenario=args.scenario, requests=args.requests, seed=args.seed,
              replicas=args.replicas, slots=args.slots, buckets=args.buckets,
              chunk=args.chunk, layers=args.layers, d_model=args.d_model,
              vocab=args.vocab, utilization=args.utilization,
              verify=not args.skip_verify)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)
    _print_record(rec)
    print(f"wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
