"""Kernel autotune driver — search tile/packing/residency caps for every
serving site × bucket and persist the winners.

    python -m repro.launch.autotune                       # TUNE_kernels.json
    python -m repro.launch.autotune --measure off         # model-rank only
    python -m repro.launch.autotune --buckets 1 8 32 --image-size 56

The search (repro.kernels.autotune) is seeded and pruned by the contract
table (repro.analysis.kernel_contracts) and its roofline cost model; on a
TPU backend the model-ranked shortlist is wall-clock measured through the
real kernels.ops wrappers, elsewhere the model ranking decides and the
table's meta records why. The output feeds `--tune` on bench_vit.py /
bench_traffic.py / serve_vit / serve_traffic, which thread the table to
every kernel call at deployment-freeze time.
"""
from __future__ import annotations

import argparse
import os

from repro.kernels import autotune as at
from repro.nn.vit import ViTConfig
from repro.utils.logging import get_logger

log = get_logger("repro.launch.autotune")


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
                    help="serving bucket set to tune for (default: the "
                         "engine's DEFAULT_BUCKETS)")
    ap.add_argument("--measure", choices=["auto", "on", "off"],
                    default="auto",
                    help="wall-clock measure the shortlist through "
                         "kernels.ops (auto: only on a TPU backend; "
                         "off-TPU the contract-model ranking decides)")
    ap.add_argument("--iters", type=int, default=20,
                    help="timed iterations per measured candidate")
    ap.add_argument("--shortlist", type=int, default=6,
                    help="model-ranked candidates measured per site")
    ap.add_argument("--out", default="TUNE_kernels.json")
    args = ap.parse_args(argv)

    cfg = ViTConfig(image_size=args.image_size, patch_size=args.patch_size,
                    n_layers=args.layers, d_model=args.d_model,
                    n_heads=args.heads, d_ff=args.d_ff or 2 * args.d_model)
    measure = {"auto": None, "on": True, "off": False}[args.measure]
    table, report = at.autotune(cfg, buckets=args.buckets, measure=measure,
                                iters=args.iters, shortlist=args.shortlist)
    table.save(args.out, report=report)

    meta = table.meta_dict
    log.info("tuned %d geometries over buckets %s (%s)", len(table),
             meta.get("buckets"), meta.get("reason"))
    for row in report:
        if row.get("winner") is None:
            log.info("%-22s %-12s b=%-3s %s (%s)", row["kernel"],
                     row["site"], row["bucket"], row["classification"],
                     row.get("note", ""))
            continue
        speedup = (row["t_model_default_s"] / row["t_model_s"]
                   if row["t_model_s"] else 1.0)
        measured = (f"  measured={row['measured_s'] * 1e6:.1f}us"
                    if row.get("measured_s") is not None else "")
        log.info("%-22s %-12s b=%-3s caps=%s blocks=%s  model %.2fx vs "
                 "default  waste %.3f→%.3f%s",
                 row["kernel"], row["site"], row["bucket"], row["winner"],
                 row["winner_blocks"], speedup,
                 row["pad_mac_waste_default"], row["pad_mac_waste"],
                 measured)
    log.info("wrote %s", os.path.abspath(args.out))


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
