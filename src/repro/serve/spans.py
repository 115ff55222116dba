"""Host spans of the serving path, on the clock a device trace uses.

One process-wide recorder. Off by default: `span(...)` then returns one
shared no-op context, with no clock call and no allocation. `enable()` turns
it on; records go into a bounded ring (the oldest are dropped and counted),
and `drain()` hands them over.

A record is `Span(name, t0_ns, t1_ns, thread, parent, batch, bucket,
n_images)`. Times are `time.time_ns()`: the wall clock that a profiler
session stamps as its start (`profile_start_time`), so a reader places the
records on the device timeline by subtracting that start. `parent` is the
span open on the same thread when this one opened, `batch` the id shared by
every span of one submitted batch (`ticket` / `batch_run`), `bucket` and
`n_images` the batch's shape where the span knows it.

The spans the serving path records, one batch on a replica thread:

- `replica.queued`: from `submit` to the worker starting the batch (closed
  on the worker's thread, parent None);
- `replica.run`: the whole batch on the worker, parent of the rest;
- `engine.put`: the images to the device as float32;
- `engine.pad`: the pad up to the bucket, only where the batch is short;
- `engine.enqueue`: the call of the bucket program until it returns;
- `engine.slice`: the padding sliced off, chunks concatenated;
- `replica.device_wait`: blocking until the logits are ready.

With the recorder on, each engine also keeps its bucket programs' HLO
instruction -> `op_name` tables (`note_program` / `programs`), so that a
device trace, which names an op by its instruction, can be read by the
model's named scopes.

Nothing here may run inside jitted code: spans are host-side only.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import re
import threading
import time
from typing import NamedTuple, Optional


class Span(NamedTuple):
    name: str
    t0_ns: int
    t1_ns: int
    thread: str
    parent: Optional[str]
    batch: Optional[int]
    bucket: Optional[int]
    n_images: Optional[int]


class Ticket(NamedTuple):
    """A submitted batch, before a worker takes it up."""
    batch: int
    t0_ns: int
    n_images: Optional[int]


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _NoSpan()
_local = threading.local()
_lock = threading.Lock()
_batch_ids = itertools.count()
_enabled = False
_ring: collections.deque = collections.deque(maxlen=1)
_dropped = 0
_programs: dict = {}


def enabled() -> bool:
    return _enabled


def enable(capacity: int = 65536):
    """Turn recording on, into a ring of the newest `capacity` records."""
    global _enabled, _ring
    with _lock:
        if _ring.maxlen != capacity:
            _ring = collections.deque(_ring, maxlen=capacity)
        _enabled = True


def disable():
    """Turn recording off and forget every record, count and table."""
    global _enabled, _dropped
    with _lock:
        _enabled = False
        _ring.clear()
        _dropped = 0
        _programs.clear()


def drain() -> list:
    """Hand over the records held, oldest first, and empty the ring."""
    with _lock:
        out = list(_ring)
        _ring.clear()
    return out


def dropped() -> int:
    """Records the ring has dropped since `enable` (oldest first)."""
    return _dropped


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _append(rec: Span):
    global _dropped
    with _lock:
        if len(_ring) == _ring.maxlen:
            _dropped += 1
        _ring.append(rec)


class _Open:
    __slots__ = ("name", "bucket", "n_images", "t0", "parent")

    def __init__(self, name, bucket, n_images):
        self.name, self.bucket, self.n_images = name, bucket, n_images

    def __enter__(self):
        stack = _stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.t0 = time.time_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.time_ns()
        _stack().pop()
        _append(Span(self.name, self.t0, t1,
                     threading.current_thread().name, self.parent,
                     getattr(_local, "batch", None), self.bucket,
                     self.n_images))
        return False


def span(name: str, bucket: Optional[int] = None,
         n_images: Optional[int] = None):
    """`with span(name): ...` records the block; a no-op while off."""
    if not _enabled:
        return _NOOP
    return _Open(name, bucket, n_images)


def ticket(n_images: Optional[int] = None) -> Optional[Ticket]:
    """A new batch id and its submission time; None while off."""
    if not _enabled:
        return None
    return Ticket(next(_batch_ids), time.time_ns(), n_images)


@contextlib.contextmanager
def _batch_run(t: Ticket):
    outer = getattr(_local, "batch", None)
    _local.batch = t.batch
    _append(Span("replica.queued", t.t0_ns, time.time_ns(),
                  threading.current_thread().name, None, t.batch, None,
                  t.n_images))
    try:
        with _Open("replica.run", None, t.n_images):
            yield
    finally:
        _local.batch = outer


def batch_run(t: Optional[Ticket]):
    """On the worker: records `replica.queued` up to now and opens
    `replica.run`; every span inside carries the ticket's batch id."""
    if t is None:
        return _NOOP
    return _batch_run(t)


# -- program tables ----------------------------------------------------------

_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ")
_REF = re.compile(r"(\w+=)?%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _scoped(op_name: str) -> bool:
    """A name-stack path (`jit(fwd)/mixer/...`), not a bare primitive."""
    return "/" in op_name


def entry_op_names(hlo_text: str) -> dict:
    """{instruction: op_name} of the ENTRY computation of a compiled HLO
    module's text. The device trace names each op it ran by these
    instruction names.

    An instruction that XLA made without a name-stack path of its own (a
    multi-output fusion, a layout copy, a prefetch of a constant) takes the
    most common path among the ops it fuses, else the path of its first
    operand that has one, else that of its first user; "" where none has
    one."""
    comps, entry, current = {}, [], None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            head = line.split(" ", 2)
            current = None
            if line.startswith("ENTRY "):
                current = entry
            elif line.endswith("{"):
                current = comps.setdefault(head[0].lstrip("%"), [])
            continue
        m = _INSTR.match(line) if current is not None else None
        if m is None:
            continue
        op = _OP_NAME.search(line)
        op = op.group(1) if op else ""
        if current is entry:
            refs = _REF.findall(line[m.end():].split(", metadata=")[0])
            entry.append((m.group(1), op,
                          [n for key, n in refs if key],
                          [n for key, n in refs if not key]))
        elif _scoped(op):
            current.append(op)
    table, users = {}, {}
    for name, op, callees, operands in entry:
        for o in operands:
            users.setdefault(o, []).append(name)
        if not _scoped(op):
            inner = [i for c in callees for i in comps.get(c, ())]
            if inner:
                op = collections.Counter(inner).most_common(1)[0][0]
            else:
                op = next((table[o] for o in operands
                           if _scoped(table.get(o, ""))), op)
        table[name] = op
    for name, *_ in reversed(entry):
        if not _scoped(table[name]):
            table[name] = next((table[u] for u in users.get(name, ())
                                if _scoped(table[u])), table[name])
    return table


def note_program(key: str, table: dict):
    """Keep a program's instruction -> op_name table under `key` (while on)."""
    if _enabled:
        with _lock:
            _programs[key] = dict(table)


def programs() -> dict:
    """{key: {instruction: op_name}} of the programs noted since `enable`."""
    with _lock:
        return {k: dict(v) for k, v in _programs.items()}
