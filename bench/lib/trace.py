"""Reduction of a profiler trace (`.xplane.pb`) to device metrics.

- Device ops: the events of the "XLA Ops" line of each TPU device plane,
  named by their HLO instruction (the TPU trace names an event by the
  instruction's whole text, `%shift_matmul_pallas.74 = f32[...] ...`).
- Busy time: the union of those events' intervals inside the traced window;
  the idle share is one minus busy time over the window.
- Kernel time: the summed durations of the events of one kernel, found by
  the name of its HLO instruction.
- Breakdown: the device ops that took most time, by name with the
  instruction number dropped, and the longest idle gaps, each labelled by
  the benchmark's host span that was open at its midpoint.

The traced window and the host spans are taken by the benchmark on the
wall clock (`SpanLog`), not by the profiler's host tracer, and placed on
the trace's clock by the session's start time, which the trace records
("Task Environment": `profile_start_time`, wall-clock ns).
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import time

import numpy as np

OPS_LINE = "XLA Ops"
ENV_PLANE = "Task Environment"
# Host spans that label idle gaps, most specific first.
SPAN_PRIORITY = ("engine_call", "submit", "fetch_result", "form_batch",
                 "offer", "wait")
_SUFFIX = re.compile(r"\.\d+$")


def instruction_name(event_name: str) -> str:
    """`%fusion.84 = s32[7872]{0} fusion(...)` -> `fusion.84`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


class SpanLog:
    """Host spans of the benchmark: `with log(name): ...` appends
    (name, start, end) in wall-clock ns. Appending to a list is safe from
    several threads."""

    def __init__(self):
        self.spans = []

    @contextlib.contextmanager
    def __call__(self, name):
        t0 = time.time_ns()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.time_ns()))


@dataclasses.dataclass
class Event:
    name: str
    start: float      # seconds, on the trace's clock
    dur: float


@dataclasses.dataclass
class Trace:
    window: tuple                     # (start, end) seconds
    ops: dict                         # device plane name -> [Event]
    spans: list                       # host [Event] of the benchmark's spans

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def device_events(self):
        for events in self.ops.values():
            yield from events

    def busy_intervals(self, plane: str) -> np.ndarray:
        """Merged (start, end) intervals of device ops inside the window."""
        lo, hi = self.window
        iv = sorted((max(e.start, lo), min(e.start + e.dur, hi))
                    for e in self.ops[plane])
        merged = []
        for s, t in iv:
            if t <= s:
                continue
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], t)
            else:
                merged.append([s, t])
        return np.array(merged).reshape(-1, 2)

    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over the devices."""
        if not self.ops:
            return 0.0
        return float(np.mean([np.sum(iv[:, 1] - iv[:, 0]) if len(iv) else 0.0
                              for iv in map(self.busy_intervals, self.ops)]))

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def kernel_events(self, pattern: str) -> list:
        """Device ops inside the window whose name matches `pattern`."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return [e for e in self.device_events()
                if rx.search(e.name) and lo <= e.start < hi]

    def top_ops(self, n: int = 10) -> list:
        lo, hi = self.window
        total = {}
        for e in self.device_events():
            if lo <= e.start < hi:
                key = _SUFFIX.sub("", e.name)
                total[key] = total.get(key, 0.0) + e.dur
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, sec / max(len(self.ops), 1)] for name, sec in ranked]

    def idle_gaps(self, n: int = 10) -> list:
        """The n longest device-idle gaps in the window, each named by the
        host span open at its midpoint ("no_span" where none was)."""
        lo, hi = self.window
        gaps = []
        for plane in self.ops:
            iv = self.busy_intervals(plane)
            edges = np.concatenate([[lo], iv.ravel(), [hi]]).reshape(-1, 2)
            for s, t in edges:
                if t > s:
                    gaps.append((t - s, 0.5 * (s + t)))
        gaps.sort(reverse=True)
        return [[self.span_at(mid), float(dur)] for dur, mid in gaps[:n]]

    def span_at(self, t: float) -> str:
        open_spans = {e.name for e in self.spans if e.start <= t < e.start + e.dur}
        for name in SPAN_PRIORITY:
            if name in open_spans:
                return name
        return "no_span"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, window_ns: tuple, spans=()) -> Trace:
    """Read one `.xplane.pb` into a Trace. `window_ns`: the traced window,
    `spans`: (name, start, end), all in wall-clock ns."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, start_ns = {}, None
    for plane in pd.planes:
        if plane.name == ENV_PLANE:
            start_ns = dict(plane.stats).get("profile_start_time")
        elif plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [Event(instruction_name(e.name),
                                             e.start_ns * 1e-9,
                                             e.duration_ns * 1e-9)
                                       for e in line.events]
    if start_ns is None:
        raise ValueError(f"{path} records no profile_start_time")

    def rel(ns):
        return (ns - start_ns) * 1e-9
    return Trace((rel(window_ns[0]), rel(window_ns[1])), ops,
                 [Event(name, rel(t0), (t1 - t0) * 1e-9)
                  for name, t0, t1 in spans])
