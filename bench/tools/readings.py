"""Readings that the output check's limits are set from: for each seed, one
run of a cell with a short window, printing the numbers the program's
answers read against the reference, and those of the control (the
reference in bfloat16 in the program's place, on the same images) with the
verdict of the cell's limits on each. All seeds run in one process, on the
chip:

    python bench/tools/readings.py --workload deit-tiny-shiftadd.bulk \
        --seeds 1 2 3 --seconds 3
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import run  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()

    run.setup_process()
    import time

    import jax

    if jax.devices()[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return 1
    for seed in args.seeds:
        res = run.run_cell(run.ROOT, args.workload, seed, args.seconds, False,
                           t_process=time.perf_counter(), with_control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_correct": res["control_correct"],
                          "program": res["numbers"],
                          "control": res["control"],
                          "metrics": {k: v["value"]
                                      for k, v in res["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
