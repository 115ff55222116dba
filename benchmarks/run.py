"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. The roofline table (§Roofline)
is produced separately from the dry-run artifacts by benchmarks/roofline.py.

  bench_kernels      — paper Fig. 4/5 + App. A (MatShift / MatAdd)
  bench_breakdown    — paper Tab. 4/6 (variant latency/energy breakdown)
  bench_energy       — paper Tab. 3 / Fig. 3 (45 nm analytic energy)
  bench_vit          — serving policy sweep (BENCH_vit.json's small twin)
  bench_serve        — LM prefill/decode serving path (BENCH_serve.json's)
  bench_traffic      — traffic frontend p99/goodput (BENCH_traffic.json's)
  check_traffic      — its gate (crossover, router-vs-shiftadd, verify keys)
  bench_elastic      — elastic control plane: autoscale + faults + degrade
  check_elastic      — its gate (zero-miss, warm-pool invariant, replay)
  bench_lm_traffic   — LM continuous batching vs static refill
  check_lm_traffic   — its gate (throughput, recompiles, bit-identity)
  bench_sensitivity  — paper Tab. 2 (trains reduced ViTs; slowest)
  bench_llloss       — paper Tab. 7 (LL-loss ablation; trains routers)
  check_analysis     — serving-contract static analyzer (pass wall-times)
  check_vit_pallas   — impl=pallas arm gate (interpret-smoke on CPU)
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# `from benchmarks import ...` needs the repo root too (namespace package;
# `python benchmarks/run.py` puts benchmarks/ itself at sys.path[0]).
sys.path.insert(1, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    from benchmarks import (bench_breakdown, bench_elastic, bench_energy,
                            bench_kernels, bench_llloss, bench_lm_traffic,
                            bench_sensitivity, bench_serve, bench_traffic,
                            bench_vit, check_analysis, check_elastic,
                            check_lm_traffic, check_traffic,
                            check_vit_pallas)

    rows = []
    for mod in (bench_kernels, bench_breakdown, bench_energy, bench_vit,
                bench_serve, bench_traffic, check_traffic, bench_elastic,
                bench_lm_traffic, bench_sensitivity, bench_llloss,
                check_analysis, check_elastic, check_lm_traffic,
                check_vit_pallas):
        t0 = time.time()
        mod.main(rows)
        rows.append((f"_{mod.__name__.split('.')[-1]}_wall",
                     (time.time() - t0) * 1e6, "harness"))
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
