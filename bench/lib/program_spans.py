"""The program's own host spans and named scopes, read for per-layer
metrics.

The program records spans of its serving path and, per bucket program, a
table from HLO instruction to `op_name`, whose path carries the model's
named scopes (`repro.serve.spans`). Its recorder is off unless turned on.

- Turning it on: `start()`, called where a reader file is loaded. The
  harness (bench/run.py) loads a run's readers before it builds the
  program, and only in a traced run, so the recorder is on for exactly the
  traced runs and holds every span of their window.
- Placing the spans: a record is stamped on the wall clock, and the trace's
  clock is the wall clock less the profiler session's start, which a reader
  is not given. It is recovered from the benchmark's `engine_call` spans,
  which are on the trace's clock: each opens a few microseconds before the
  program's `engine.put` of the same call, one for one, so the two lists
  align from their ends (the program's also holds the warm-up's calls) and
  their median difference is the start. Where fewer than half the pairs
  agree within `AGREE_S`, the placement is refused and the readers read
  nothing.
- A program without the recorder (an older commit) gives nothing to read,
  and every function here returns None.
"""
from __future__ import annotations

import collections
import weakref

import numpy as np

try:
    from repro.serve import spans as _spans
except ImportError:
    _spans = None

ENGINE_SPANS = ("engine.put", "engine.pad", "engine.enqueue", "engine.slice")
# Pairs of engine_call and engine.put starts that agree this closely (s)
# place the program's spans on the trace.
AGREE_S = 1e-4
_cache = {"ctx": None, "value": None}


def start():
    """Turn the program's span recorder on, where the program has one."""
    if _spans is not None and not _spans.enabled():
        _spans.enable()


def _trace_start(trace, recs):
    """(ref_ns, offset_s): a record's time on the trace's clock is
    (t_ns - ref_ns) * 1e-9 - offset_s. None where the spans do not align."""
    calls = sorted(e.start for e in trace.spans if e.name == "engine_call")
    puts = sorted(r.t0_ns for r in recs if r.name == "engine.put")
    k = min(len(calls), len(puts))
    if k == 0:
        return None
    ref = puts[-1]
    d = (np.array(puts[-k:], dtype=np.int64) - ref) * 1e-9 - np.array(calls[-k:])
    offset = float(np.median(d))
    if np.mean(np.abs(d - offset) < AGREE_S) < 0.5:
        return None
    return ref, offset


def placed(ctx):
    """The program's spans of this run as [(name, start, end, batch)] on the
    trace's clock (s), or None. Drains the recorder once per run."""
    if _spans is None or ctx.trace is None:
        return None
    cached = _cache["ctx"]
    if cached is not None and cached() is ctx:
        return _cache["value"]
    recs = _spans.drain()
    clock = _trace_start(ctx.trace, recs)
    value = None
    if clock is not None:
        ref, offset = clock
        value = [(r.name, (r.t0_ns - ref) * 1e-9 - offset,
                  (r.t1_ns - ref) * 1e-9 - offset, r.batch) for r in recs]
    _cache["ctx"], _cache["value"] = weakref.ref(ctx), value
    return value


def per_batch_ms(ctx, names):
    """Mean over the batches that open a span named in `names` inside the
    traced window of the summed time (ms) of those spans."""
    spans = placed(ctx)
    if spans is None:
        return None
    lo, hi = ctx.trace.window
    per_batch = collections.Counter()
    for name, t0, t1, batch in spans:
        if name in names and lo <= t0 < hi:
            per_batch[batch] += t1 - t0
    if not per_batch:
        return None
    return 1e3 * sum(per_batch.values()) / len(per_batch)


def _union(intervals):
    merged = []
    for s, t in sorted(intervals):
        if t <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return merged


def _overlap(a, b) -> float:
    """Total length of the intersection of two sorted disjoint lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_while_open(ctx, names):
    """Share (%) of the traced window in which the device is idle while a
    span named in `names` is open, averaged over the device planes."""
    spans = placed(ctx)
    if spans is None or not ctx.trace.ops:
        return None
    lo, hi = ctx.trace.window
    open_iv = _union((max(t0, lo), min(t1, hi)) for name, t0, t1, _ in spans
                     if name in names)
    if not open_iv:
        return None
    total = sum(t - s for s, t in open_iv)
    shares = [total - _overlap(open_iv, ctx.trace.busy_intervals(p).tolist())
              for p in ctx.trace.ops]
    return 100.0 * float(np.mean(shares)) / ctx.trace.window_s


def scope_table(ctx):
    """{instruction: op_name} of the bucket programs the window served (the
    most served first), or None where the program noted none."""
    if _spans is None:
        return None
    programs = _spans.programs()
    served = collections.Counter(b[0] for b in ctx.window.batches)
    table = {}
    for bucket, _ in served.most_common():
        for name, op in programs.get(f"jit_fwd/{bucket}", {}).items():
            table.setdefault(name, op)
    return table or None


def scope_ms(ctx, scopes):
    """Device time (ms) per bucket-program run of the ops whose `op_name`
    path holds one of `scopes`, in the traced window; None where no op of
    the program lies under them.

    The runs are counted as the median number of events per instruction of
    the program (each runs once per forward; the median is not moved by
    the few instruction names that other programs share)."""
    table = scope_table(ctx)
    if table is None or ctx.trace is None:
        return None
    wanted = {name for name, op in table.items()
              if any(s in op.split("/") for s in scopes)}
    if not wanted:
        return None
    lo, hi = ctx.trace.window
    events = [e for e in ctx.trace.device_events()
              if lo <= e.start < hi and e.name in table]
    if not events:
        return None
    runs = float(np.median(list(collections.Counter(
        e.name for e in events).values())))
    return 1e3 * sum(e.dur for e in events if e.name in wanted) / runs
