"""The measured window: closed-loop clients offer requests to the program's
scheduler on the wall clock, formed batches go to the program's replicas,
and each request completes when its last part's logits are back on the
host.

One dispatcher (the calling thread) does what a server's front end does:
offer due requests (`MicroBatchScheduler.offer`), form batches while a
replica slot is free (`form_batch`), submit them (`replicas.submit`), and
hand results back. Every request is timed from the moment it was due: the
completion of the client's previous one (the window's start for the first).
"""
from __future__ import annotations

import concurrent.futures
import contextlib
import dataclasses
import time

import numpy as np

from bench.lib.traffic import payload_indices

# How long after the window closes the dispatcher waits for answers still
# due; a request unanswered by then has failed.
DRAIN_S = 60.0


@dataclasses.dataclass
class ReqState:
    rid: int
    due: float
    arrival: object          # traffic.Arrival
    offered: float = 0.0
    done: float = float("inf")
    parts_left: int = 0
    parts: dict = dataclasses.field(default_factory=dict)

    def logits(self) -> np.ndarray:
        return np.concatenate([self.parts[i] for i in sorted(self.parts)])


@dataclasses.dataclass
class WindowResult:
    t_start: float
    t_end: float
    requests: list           # ReqState of every request due in the window
    batches: list            # (bucket, n_images, reason, submitted, done)

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start

    def images_in_window(self) -> int:
        return sum(r.arrival.size for r in self.requests
                   if r.done <= self.t_end)

    def images_per_s_until(self, t: float) -> float:
        """Images of requests completed from the window's start to `t`, per
        second of that span."""
        done = sum(r.arrival.size for r in self.requests if r.done <= t)
        return done / (t - self.t_start)

    def latencies_s(self) -> np.ndarray:
        """Due-to-done seconds of every request; inf for one never done."""
        return np.array([r.done - r.due for r in self.requests])

    def lateness_s(self) -> np.ndarray:
        return np.array([r.offered - r.due for r in self.requests])


class _NoSpans:
    def __call__(self, name):
        return contextlib.nullcontext()


def run_window(replicas, scheduler, pool: np.ndarray, clients,
               seconds: float, *, span=None) -> WindowResult:
    """Drive one window of `clients` (a `traffic.ClosedClients`).
    `span(name)`: a context manager per host span (the profiler's
    annotations in a traced run)."""
    from repro.serve.traffic import Request

    span = span or _NoSpans()
    n_pool = len(pool)
    states, batches = {}, []
    free = list(range(replicas.n_slots))
    inflight = {}                                # future -> (slot, batch, t)
    next_rid = 0

    def new_request(due, client):
        nonlocal next_rid
        arrival = clients.next(client)
        rid = next_rid
        next_rid += 1
        st = ReqState(rid, due, arrival)
        states[rid] = st
        req = Request(rid=rid, arrival_s=due, size=arrival.size,
                      klass=arrival.klass, deadline_s=due + arrival.budget_s,
                      seed=client)
        now = time.perf_counter()
        with span("offer"):
            scheduler.offer(req, now)
        st.offered = now
        st.parts_left = -(-arrival.size // scheduler.buckets[-1])

    t_start = time.perf_counter()
    t_end = t_start + seconds
    for c in range(clients.n_clients):
        new_request(t_start, c)

    while True:
        done_futs = [f for f in inflight if f.done()]
        for fut in done_futs:
            slot, batch, t_sub = inflight.pop(fut)
            free.append(slot)
            with span("fetch_result"):
                logits, _ = fut.result()
                logits = np.asarray(logits)
            t_done = time.perf_counter()
            batches.append((batch.bucket, batch.n_images, batch.reason,
                            t_sub, t_done))
            row = 0
            for part in batch.parts:
                st = states[part.rid]
                if st.arrival.check:
                    st.parts[part.part_idx] = logits[row:row + part.size]
                row += part.size
                st.parts_left -= 1
                if st.parts_left == 0:
                    st.done = t_done
                    if t_done < t_end:
                        new_request(t_done, part.req.seed)

        now = time.perf_counter()
        draining = now >= t_end
        while free:
            with span("form_batch"):
                batch = scheduler.form_batch(now, drain=draining)
            if batch is None:
                break
            idx = np.concatenate([
                payload_indices(states[p.rid].arrival.img_offset, p.offset,
                                p.size, n_pool) for p in batch.parts])
            slot = free.pop()
            with span("submit"):
                images = pool[idx]
                t_sub = time.perf_counter()
                fut = replicas.submit(slot, images)
            inflight[fut] = (slot, batch, t_sub)

        if draining and not inflight and not scheduler.has_queued():
            break
        if now > t_end + DRAIN_S:
            break
        # Sleep until the window's end, a forced dispatch or a completion.
        wake = [t_end] if not draining else []
        forced = scheduler.next_forced_dispatch_s()
        if forced is not None and free:
            wake.append(forced)
        timeout = max(0.0, min(wake) - time.perf_counter()) if wake else 0.05
        with span("wait"):
            if inflight:
                concurrent.futures.wait(list(inflight), timeout=timeout,
                                        return_when="FIRST_COMPLETED")
            elif timeout > 0:
                time.sleep(timeout)

    due = [s for s in states.values() if s.due < t_end]
    return WindowResult(t_start, t_end, due, batches)
