"""LM traffic-serving driver: token-level continuous batching vs the static
fixed-batch refill baseline, on the SAME seeded trace and the SAME engines.

    python -m repro.launch.serve_lm_traffic --scenario poisson --policy stage1
    python -m repro.launch.serve_lm_traffic --scenario bursty --policy all --slots 8
    python -m repro.launch.serve_lm_traffic --requests 120 --utilization 2.0

A seeded trace (`--scenario poisson|bursty|diurnal`) of variable-length,
deadline-classed LM requests (prompt tokens and decode lengths derived
deterministically from each request's seed — serve.traffic's LM payload
helpers) is pushed through `serve.scheduler.SlotScheduler` onto `--replicas`
`BucketedLMEngine`s of `--slots` decode slots each. Requests join the
RUNNING decode batch at chunk boundaries via the jitted admit/evict slot
scatters; the static arm re-serves the identical trace under gang-refill
admission on the same warmed pool. Offered load and deadline budgets are
calibrated from measured per-bucket prefill + decode-chunk times, so the
virtual timeline is machine-independent up to the calibration. Writes
BENCH_lm_traffic.json and exits non-zero if any program recompiled after
warmup or a determinism verification failed.
"""
from __future__ import annotations

import argparse
import json
import os

from repro.serve.frontend import lm_traffic_sweep
from repro.serve.traffic import SCENARIOS
from repro.utils.logging import get_logger

log = get_logger("repro.launch.serve_lm_traffic")

POLICIES = ("stage1", "shiftadd")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="poisson", choices=SCENARIOS)
    ap.add_argument("--policy", default="stage1",
                    choices=list(POLICIES) + ["all"])
    ap.add_argument("--requests", type=int, default=60)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--buckets", type=int, nargs="+", default=[4, 8, 16])
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--utilization", type=float, default=1.5,
                    help="offered load as a fraction of the calibrated "
                         "full-occupancy request capacity (>1 = overload, "
                         "where continuous batching pays off)")
    ap.add_argument("--new-tokens", type=int, nargs=2, default=[4, 24],
                    metavar=("LO", "HI"))
    ap.add_argument("--skip-verify", action="store_true")
    ap.add_argument("--out", default="BENCH_lm_traffic.json")
    args = ap.parse_args(argv)

    policies = POLICIES if args.policy == "all" else (args.policy,)
    rec = lm_traffic_sweep(
        scenario=args.scenario, policies=policies, n_requests=args.requests,
        seed=args.seed, n_replicas=args.replicas, n_slots=args.slots,
        prompt_buckets=tuple(args.buckets), chunk=args.chunk,
        layers=args.layers, d_model=args.d_model, vocab_size=args.vocab,
        utilization=args.utilization,
        new_token_range=tuple(args.new_tokens),
        verify_replay=not args.skip_verify,
        verify_serial_oracle=not args.skip_verify)

    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)
    bad = 0
    for name, r in rec["policies"].items():
        c, s = r["continuous"], r["static"]
        log.info(
            "%s: continuous %.1f tok/s (occupancy %.2f, ttft p50 %.1f ms) "
            "vs static %.1f tok/s (occupancy %.2f) — %.3fx",
            name, c["tokens_per_s"], c["chunk_occupancy"],
            c["ttft"]["p50_s"] * 1e3, s["tokens_per_s"],
            s["chunk_occupancy"], r["continuous_vs_static_tokens_per_s"])
        bad += c["recompiles_after_warmup"] + s["recompiles_after_warmup"]
        for key in ("replay_bit_identical_logits",
                    "one_vs_n_bit_identical_logits"):
            if key in r and not r[key]:
                log.error("%s: %s is FALSE", name, key)
                bad += 1
    log.info("wrote %s", os.path.abspath(args.out))
    return 1 if bad else 0


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
