"""What a per-layer metric reader is given, and the arithmetic that several
readers share."""
from __future__ import annotations

import collections
import dataclasses

from bench.lib import cost


@dataclasses.dataclass
class Context:
    cfg: dict                 # the configuration file
    window: object            # serve_loop.WindowResult
    trace: object             # trace.Trace, or None in an untraced run
    peaks: dict               # the device's row of peaks.json
    images_per_s: float       # images completed per second before the
                              # profiler started (the untraced part)
    family: object = None     # spec.Family of the configuration


def kernel_roofline(ctx: Context, pattern: str, calls_fn, cost_fn):
    """Share (%) of the roofline that a kernel's device time reaches in the
    traced window, or None where the trace holds no event of it.

    calls_fn(cfg, bucket) gives the shapes of the kernel's calls in one
    forward at `bucket`, cost_fn(*shape) their (ops, bytes). The kernel's
    events are spread over buckets in the proportion in which the window
    served them; with one bucket the count is exact."""
    if ctx.trace is None:
        return None
    events = ctx.trace.kernel_events(pattern)
    if not events:
        return None
    served = collections.Counter(b[0] for b in ctx.window.batches)
    total = sum(served.values())
    calls_per_fwd = 0.0
    roof_per_fwd = 0.0
    for bucket, n in served.items():
        calls = calls_fn(ctx.cfg, bucket)
        calls_per_fwd += n / total * len(calls)
        roof_per_fwd += n / total * sum(
            cost.roofline_s(*cost_fn(*shape), ctx.peaks["bf16_flops_per_s"],
                            ctx.peaks["hbm_bytes_per_s"]) for shape in calls)
    measured = sum(e.dur for e in events)
    return 100.0 * roof_per_fwd * len(events) / calls_per_fwd / measured
