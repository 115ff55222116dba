"""One traced run of a cell that keeps the profiler's files, packed into
`chiprun_out/<cell>.trace.tgz`: the raw material of the trace reduction's
test fixture (`tests/bench/data/`), and a trace to look at by hand.

    python bench/tools/trace_sample.py --workload deit-tiny-shiftadd.bulk \
        --seed 1 --seconds 2
"""
from __future__ import annotations

import argparse
import json
import sys
import tarfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import run  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()

    run.setup_process()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("trace_sample: needs a TPU", file=sys.stderr)
        return 1
    try:
        res = run.run_cell(run.ROOT, args.workload, args.seed, args.seconds,
                           True, t_process=time.perf_counter(),
                           keep_trace=True)
        print(json.dumps(res), flush=True)
    finally:
        out = run.ROOT / "chiprun_out"
        out.mkdir(exist_ok=True)
        with tarfile.open(out / f"{args.workload}.trace.tgz", "w:gz") as tar:
            tar.add(run.WORK / "trace" / args.workload, arcname=args.workload)
    return 0


if __name__ == "__main__":
    sys.exit(main())
