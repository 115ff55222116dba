"""Engine replicas for the traffic frontend: thread-pool and data-parallel.

The scheduler sees `n_slots` identical logical servers; how a slot maps to
hardware is this module's concern:

- **ThreadPoolReplicas** (the CPU arm): R slots served by a
  `ThreadPoolExecutor`. By default all slots share ONE
  `BucketedViTEngine` — a jitted executable is stateless and thread-safe,
  so sharing keeps warmup at one compile per bucket no matter how many
  replicas. `share_engine=False` builds one engine per slot (full isolation,
  R× the warmup compiles — the shape a future multi-process pool takes).
  1-vs-N logit parity does NOT depend on the sharing, nor on replicas
  forming the same batches: the engine forward is batch-invariant per image
  (per-image MoE capacity dispatch, serve/vision.py), so per-request logits
  are bit-identical across replica counts even when batch compositions
  diverge — the `one_vs_n_bit_identical_logits` gate asserts exactly this.

- **DataParallelReplicas** (the multi-device arm): ONE slot whose engine
  shards every batch row-wise across a `("data",)` device mesh via
  `distributed.sharding.batch_sharding` — the repo's `batch → data` logical
  rule, reused by the vision path. Parallelism here accelerates each batch
  (the calibrated service model picks the speedup up automatically) instead
  of multiplying concurrent batches. Buckets are rounded up to multiples of
  the device count by the engine; read the effective set off
  `pool.buckets`. Row-sharding composes with the per-image dispatch (a
  row's routing reads only that row), so sharded logits are bit-identical
  to the single-device path — shiftadd included, pinned by the
  data-parallel arm test in tests/test_traffic_serve.py.

`make_replicas(..., arm="auto")` picks data-parallel when the backend has
enough devices, else the thread pool — so the same frontend code serves a
laptop CPU and a multi-device accelerator host.

All submissions return `concurrent.futures.Future`s; the frontend's virtual
clock never blocks on one until its completion event fires, so thread-pool
replicas genuinely overlap engine execution.

With the span recorder on (`serve.spans`), each batch records
`replica.queued` (submit to worker start), `replica.run` around the engine
call and `replica.device_wait` (blocking on the logits), all under one
batch id.
"""
from __future__ import annotations

import concurrent.futures
import time

import jax

from repro.serve import spans
from repro.serve.vision import DEFAULT_BUCKETS, BucketedViTEngine


class _ReplicaBase:
    engines: list
    n_slots: int

    @property
    def buckets(self):
        return self.engines[0].buckets

    @property
    def trace_count(self) -> int:
        return sum(e.trace_count for e in self.engines)

    def warmup(self):
        for e in self.engines:
            e.warmup()
        return self

    def close(self):
        pass


class ThreadPoolReplicas(_ReplicaBase):
    arm = "thread"

    def __init__(self, model, params, n_replicas=2, buckets=DEFAULT_BUCKETS,
                 freeze=True, impl=None, tune=None, share_engine=True):
        assert n_replicas >= 1
        n_engines = 1 if share_engine else n_replicas
        self.engines = [BucketedViTEngine(model, params, buckets=buckets,
                                          freeze=freeze, impl=impl, tune=tune)
                        for _ in range(n_engines)]
        self.n_slots = n_replicas
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=n_replicas, thread_name_prefix="vit-replica")
        self._closed = False

    @property
    def closed(self) -> bool:
        return self._closed

    def _engine_for(self, slot: int) -> BucketedViTEngine:
        return self.engines[slot % len(self.engines)]

    def submit(self, slot: int, images) -> concurrent.futures.Future:
        """Future resolving to (logits, measured wall seconds)."""
        if self._closed:
            raise RuntimeError("submit() on a closed ThreadPoolReplicas")
        engine = self._engine_for(slot)
        batch = spans.ticket(len(images))

        def run():
            with spans.batch_run(batch):
                t0 = time.perf_counter()
                logits = engine.infer(images)
                with spans.span("replica.device_wait"):
                    logits = jax.block_until_ready(logits)
                return logits, time.perf_counter() - t0

        return self._pool.submit(run)

    def close(self):
        """Idempotent shutdown: waits for in-flight submissions (their
        Futures stay resolvable after close), then marks the pool closed —
        a second close is a no-op and a submit after close raises rather
        than silently queueing onto a dead executor."""
        if self._closed:
            return
        self._closed = True
        self._pool.shutdown(wait=True)


class DataParallelReplicas(_ReplicaBase):
    arm = "sharded"

    def __init__(self, model, params, n_replicas=2, buckets=DEFAULT_BUCKETS,
                 freeze=True, impl=None, tune=None, devices=None):
        devices = list(devices if devices is not None else jax.devices())
        if len(devices) < n_replicas:
            raise ValueError(
                f"data-parallel arm needs {n_replicas} devices, backend has "
                f"{len(devices)} — use the thread arm (or arm='auto')")
        from repro.distributed.sharding import make_mesh
        mesh = make_mesh((n_replicas,), ("data",),
                         devices=devices[:n_replicas])
        self.mesh = mesh
        self.engines = [BucketedViTEngine(model, params, buckets=buckets,
                                          freeze=freeze, impl=impl, tune=tune,
                                          mesh=mesh)]
        self.n_slots = 1        # one logical server, n× per-batch speed

    def submit(self, slot: int, images) -> concurrent.futures.Future:
        """Future resolving to (logits, measured wall seconds); the sharded
        arm executes synchronously (one device set, one program at a time)."""
        fut = concurrent.futures.Future()
        with spans.batch_run(spans.ticket(len(images))):
            t0 = time.perf_counter()
            logits = self.engines[0].infer(images)
            with spans.span("replica.device_wait"):
                logits = jax.block_until_ready(logits)
            seconds = time.perf_counter() - t0
        fut.set_result((logits, seconds))
        return fut


class LMReplicas:
    """R independent `BucketedLMEngine`s for token-level continuous batching.

    Unlike the ViT pool, LM engines are STATEFUL — the packed slot array
    (recurrent carries / KV rows / conv windows) lives in the engine — so
    replicas never share one: each replica owns its slot array and its own
    compiled programs. The frontend (`serve.frontend.serve_lm_trace`)
    advances one virtual timeline per engine and hands a queued request to
    whichever engine reaches a chunk boundary with a free slot first
    (ties: lowest index) — deterministic dispatch, same contract as the
    vision pool's lowest-idle-slot rule.
    """

    arm = "lm"

    def __init__(self, model, params, n_replicas=1, **engine_kw):
        from repro.serve.lm import BucketedLMEngine

        assert n_replicas >= 1
        self.engines = [BucketedLMEngine(model, params, **engine_kw)
                        for _ in range(n_replicas)]
        self.n_replicas = n_replicas

    @property
    def prompt_buckets(self):
        return self.engines[0].prompt_buckets

    @property
    def chunk(self) -> int:
        return self.engines[0].chunk

    @property
    def n_slots(self) -> int:
        return self.engines[0].n_slots

    @property
    def trace_count(self) -> int:
        return sum(e.trace_count for e in self.engines)

    @property
    def prefill_trace_count(self) -> int:
        return sum(e.prefill_trace_count for e in self.engines)

    @property
    def expected_programs(self) -> int:
        return sum(e.expected_programs for e in self.engines)

    def warmup(self):
        for e in self.engines:
            e.warmup()
        return self

    def reset(self):
        """Fresh slot arrays everywhere (no new programs)."""
        for e in self.engines:
            e.reset()
        return self

    def close(self):
        pass


def make_lm_replicas(model, params, n_replicas=1, **engine_kw):
    """LM pool factory, mirroring `make_replicas` for the vision arms.
    engine_kw forwards to BucketedLMEngine (n_slots, prompt_buckets, chunk,
    max_len)."""
    return LMReplicas(model, params, n_replicas=n_replicas, **engine_kw)


def make_replicas(model, params, n_replicas=2, arm="auto", **kw):
    """arm: 'thread' | 'sharded' | 'auto' (sharded when the backend has
    ≥ n_replicas devices and n_replicas > 1, else thread)."""
    if arm == "auto":
        arm = ("sharded" if n_replicas > 1
               and len(jax.devices()) >= n_replicas else "thread")
    cls = {"thread": ThreadPoolReplicas, "sharded": DataParallelReplicas}[arm]
    return cls(model, params, n_replicas=n_replicas, **kw)
