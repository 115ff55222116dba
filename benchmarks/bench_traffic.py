"""Traffic-serving benchmark: one seeded arrival trace, every policy arm.
Writes BENCH_traffic.json — the per-REQUEST twin of BENCH_vit.json's
per-batch numbers, sharing its latency-summary schema (serve.metrics).

    PYTHONPATH=src python benchmarks/bench_traffic.py [--requests 300]
    PYTHONPATH=src python benchmarks/bench_traffic.py --scenario bursty

The trace (arrival rate, deadline budgets) is calibrated from the DENSE
arm's measured per-bucket service times at --utilization of its replica
capacity, then replayed unchanged against each policy — so
`shiftadd_vs_dense_p99` compares the same requests, same arrivals, same
deadlines, and reflects purely how much faster the reparameterized engine
drains the queue. CI gates (benchmarks/check_traffic.py): zero recompiles
after warmup, zero deadline misses at the calibrated default load, shiftadd
p99 at or below dense p99, bit-identical seeded replay on EVERY arm
(shiftadd's MoE included — per-image capacity dispatch made it
batch-invariant), and 1-vs-N-replica bit-identical per-request logits under
diverging batch compositions (`one_vs_n_bit_identical_logits`). The sweep
also carries the telemetry-trained `router` arm (shiftadd weights, router
fine-tuned on measured per-expert latencies — serve.telemetry +
train.router_tune), gated router p99 ≤ shiftadd p99 with increased shift
expert token share.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.nn.vit import ViTConfig
from repro.serve.frontend import traffic_sweep
from repro.serve.traffic import SCENARIOS


def run(scenario="poisson", requests=300, seed=0, replicas=2, arm="auto",
        utilization=0.4, image_size=56, layers=4, d_model=128, impl=None,
        tune=None, verify_replay=True, verify_one_vs_n=True, telemetry=None,
        router_steps=40):
    # "router" is the telemetry-trained arm: shiftadd weights, measured
    # per-expert latencies (TELEMETRY_experts.json or in-process probes),
    # router fine-tuned against them (serve.frontend docstring).
    cfg = ViTConfig(image_size=image_size, n_layers=layers, d_model=d_model,
                    d_ff=2 * d_model)
    return traffic_sweep(
        cfg, scenario=scenario,
        policies=("dense", "stage1", "shiftadd", "router"),
        n_requests=requests, seed=seed, replicas=replicas, arm=arm,
        utilization=utilization, impl=impl, tune=tune,
        verify_replay=verify_replay, verify_one_vs_n=verify_one_vs_n,
        telemetry=telemetry, router_steps=router_steps)


def pallas_arm(scenario="poisson", requests=300, seed=0, tune=None,
               image_size=56, layers=4, d_model=128):
    """Nested `pallas_arm` traffic record: the shiftadd arm served at
    impl=pallas next to an impl=xla twin on the SAME trace geometry.

    TPU: real kernels at the CLI geometry. Elsewhere: interpret-mode smoke
    at bench_vit.SMOKE_CFG-scale traffic (40 requests, 16px, 2 layers) —
    path proof only; check_vit_pallas.py skips the latency gate with the
    carried reason.
    """
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        mode, kernel_impl, skip_reason = "tpu", "pallas", None
        geo = dict(image_size=image_size, layers=layers, d_model=d_model)
        n_req = requests
    else:
        mode, kernel_impl = "interpret-smoke", "interpret"
        skip_reason = (f"backend={backend}: Pallas kernels ran under the "
                       "interpreter at reduced traffic geometry; timings "
                       "are interpreter overhead, not kernel performance")
        geo = dict(image_size=16, layers=2, d_model=32)
        n_req = 40
    cfg = ViTConfig(image_size=geo["image_size"], n_layers=geo["layers"],
                    d_model=geo["d_model"], d_ff=2 * geo["d_model"])
    common = dict(scenario=scenario, policies=("shiftadd",),
                  n_requests=n_req, seed=seed, replicas=1, arm="thread",
                  verify_replay=False, verify_one_vs_n=False)
    rec_pallas = traffic_sweep(cfg, impl=kernel_impl, tune=tune, **common)
    rec_xla = traffic_sweep(cfg, impl="xla", tune=None, **common)
    return {
        "mode": mode,
        "backend": backend,
        "impl": kernel_impl,
        "tuned": tune is not None,
        "skip_reason": skip_reason,
        "geometry": dict(geo, requests=n_req),
        "pallas": rec_pallas,
        "xla": rec_xla,
    }


def main(rows=None):
    if rows is not None:
        # benchmarks/run.py harness mode: tiny geometry, CSV row contract.
        rec = run(requests=40, image_size=16, layers=2, d_model=32,
                  verify_replay=False, verify_one_vs_n=False)
        for name, r in rec["policies"].items():
            rows.append((f"traffic_{name}_p99", r["latency"]["p99_s"] * 1e6,
                         f"goodput_img_s={r['goodput_images_per_s']:.1f}"))
        return

    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="poisson", choices=SCENARIOS)
    ap.add_argument("--requests", type=int, default=300)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--arm", default="auto",
                    choices=["auto", "thread", "sharded"])
    ap.add_argument("--utilization", type=float, default=0.4)
    ap.add_argument("--image-size", type=int, default=56)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--impl", choices=["xla", "pallas", "interpret"],
                    default=None)
    ap.add_argument("--tune", default=None, metavar="TUNE_kernels.json",
                    help="persisted autotune table (launch/autotune.py "
                         "output)")
    ap.add_argument("--telemetry", default=None,
                    metavar="TELEMETRY_experts.json",
                    help="persisted expert telemetry (launch/tune_router.py "
                         "output) for the router arm; absent/invalid → "
                         "extracted in-process (fail-open)")
    ap.add_argument("--router-steps", type=int, default=40,
                    help="router fine-tune steps for the telemetry arm")
    ap.add_argument("--skip-pallas-arm", action="store_true",
                    help="omit the nested impl=pallas traffic arm")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.out is None:
        args.out = os.path.join(os.path.dirname(__file__), "..",
                                "BENCH_traffic.json")
    # --impl threads explicitly through traffic_sweep → replicas → engines
    # (never via ops.set_default_impl; satellite bugfix).
    tune = None
    if args.tune:
        from repro.kernels import autotune
        tune = autotune.load_table(args.tune)
        if tune is None:
            print(f"WARNING: could not load tune table {args.tune}; "
                  f"serving with default block caps")

    telemetry = None
    if args.telemetry:
        from repro.serve.telemetry import load_telemetry
        telemetry = load_telemetry(args.telemetry)
        if telemetry is None:
            print(f"WARNING: could not load telemetry {args.telemetry}; "
                  f"the router arm will extract its own probes")

    rec = run(scenario=args.scenario, requests=args.requests, seed=args.seed,
              replicas=args.replicas, arm=args.arm,
              utilization=args.utilization, image_size=args.image_size,
              layers=args.layers, d_model=args.d_model, impl=args.impl,
              tune=tune, telemetry=telemetry, router_steps=args.router_steps)
    if not args.skip_pallas_arm:
        rec["pallas_arm"] = pallas_arm(
            scenario=args.scenario, requests=args.requests, seed=args.seed,
            tune=tune, image_size=args.image_size, layers=args.layers,
            d_model=args.d_model)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)
    for name, r in rec["policies"].items():
        lat = r["latency"]
        print(f"{name:>9}: p50 {lat['p50_s'] * 1e3:7.1f} ms  "
              f"p95 {lat['p95_s'] * 1e3:7.1f} ms  "
              f"p99 {lat['p99_s'] * 1e3:7.1f} ms  "
              f"goodput {r['goodput_images_per_s']:8.1f} img/s  "
              f"miss {r['deadline_miss_rate']:.3f}  "
              f"waste {r['padding_waste']:.3f}  "
              f"recompiles {r['recompiles_after_warmup']}")
    if "shiftadd_vs_dense_p99" in rec:
        print(f"shiftadd vs dense p99: {rec['shiftadd_vs_dense_p99']:.3f}x")
    if "router_vs_shiftadd_p99" in rec:
        ro = rec["policies"]["router"]
        sa_share = rec["policies"]["shiftadd"].get(
            "expert_token_share", {}).get("shift", 0.0)
        ro_share = ro.get("expert_token_share", {}).get("shift", 0.0)
        print(f"router vs shiftadd p99: "
              f"{rec['router_vs_shiftadd_p99']:.3f}x  "
              f"shift share {sa_share:.3f} → {ro_share:.3f}  "
              f"(alpha source {ro.get('expert_latency_source')}, "
              f"{ro.get('router_steps')} steps)")
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
