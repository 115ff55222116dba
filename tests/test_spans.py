"""The serving path's span recorder (`serve/spans.py`) and the model's named
scopes: off it records nothing and costs well under a microsecond a span;
on it records each batch's spans with their thread, parent and batch id; the
ring keeps the newest records; every op of a bucket program lies under a
named scope."""
import dataclasses
import re
import sys
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.policy import DENSE
from repro.nn.vit import ShiftAddViT, ViTConfig
from repro.serve import spans
from repro.serve.replicas import ThreadPoolReplicas
from repro.serve.vision import BucketedViTEngine, build_policy_model

sys.path.insert(0, str(Path(__file__).parent / "bench"))
from bench_fixtures import TINY  # noqa: E402

TOP_SCOPES = ("patch_embed", "mixer", "feed", "head")
MOE_SCOPES = ("moe_dispatch", "expert_mult", "expert_shift", "moe_combine")


@pytest.fixture
def recorder():
    spans.disable()
    spans.enable()
    yield spans
    spans.disable()


def tiny_model(arm):
    cfg = ViTConfig(**{k: TINY[k] for k in (
        "image_size", "patch_size", "in_channels", "n_classes", "n_layers",
        "d_model", "n_heads", "d_ff")}, moe_capacity=TINY["moe_capacity_factor"])
    dense = ShiftAddViT(dataclasses.replace(cfg, policy=DENSE))
    return build_policy_model(cfg, arm, dense, dense.init(jax.random.PRNGKey(0)))


def images(n):
    s = TINY["image_size"]
    return np.random.default_rng(n).integers(0, 256, (n, s, s, 3), np.uint8)


def test_off_records_nothing_and_hands_out_the_shared_no_op():
    spans.disable()
    assert spans.span("engine.put") is spans.span("replica.run", 8, 8)
    assert spans.ticket(8) is None
    assert spans.batch_run(None) is spans.span("x")
    with spans.span("engine.put"):
        pass
    spans.note_program("jit_fwd/8", {"fusion.1": "jit(fwd)/mixer/add"})
    assert spans.drain() == [] and spans.programs() == {}


def test_off_span_costs_under_a_microsecond():
    spans.disable()
    n = 100_000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            with spans.span("engine.enqueue", 32, 32):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 1e-6, best


def test_on_records_batch_parent_and_thread_across_replica_threads(recorder):
    model, params = tiny_model("dense")
    reps = ThreadPoolReplicas(model, params, n_replicas=2, buckets=(4, 8))
    reps.warmup()
    recorder.drain()
    barrier = threading.Barrier(2)
    engine = reps.engines[0]
    infer = engine.infer

    def both_threads(x):
        barrier.wait(timeout=30)       # the two batches run side by side
        return infer(x)
    engine.infer = both_threads
    futs = [reps.submit(0, images(8)), reps.submit(1, images(3))]
    for f in futs:
        f.result()
    reps.close()
    recs = recorder.drain()
    by_batch = {}
    for r in recs:
        by_batch.setdefault(r.batch, []).append(r)
    assert len(by_batch) == 2 and None not in by_batch
    threads = set()
    for batch, rs in by_batch.items():
        names = [r.name for r in rs]
        n = rs[0].n_images
        assert sorted(set(names)) == sorted(
            {"replica.queued", "replica.run", "engine.put", "engine.enqueue",
             "engine.slice", "replica.device_wait"}
            | ({"engine.pad"} if n == 3 else set()))
        run = next(r for r in rs if r.name == "replica.run")
        for r in rs:
            assert r.parent == (None if r.name in ("replica.queued",
                                                   "replica.run")
                                else "replica.run"), r
            assert r.thread == run.thread
            assert run.t0_ns <= r.t0_ns <= r.t1_ns <= run.t1_ns or (
                r.name == "replica.queued" and r.t1_ns <= run.t0_ns)
        enq = next(r for r in rs if r.name == "engine.enqueue")
        assert (enq.bucket, enq.n_images) == ((8, 8) if n == 8 else (4, 3))
        threads.add(run.thread)
    assert len(threads) == 2
    assert all(t.startswith("vit-replica") for t in threads)


def test_ring_keeps_the_newest_records_and_counts_the_dropped(recorder):
    recorder.enable(capacity=4)
    for i in range(10):
        with recorder.span(f"s{i}"):
            pass
    assert [r.name for r in recorder.drain()] == ["s6", "s7", "s8", "s9"]
    assert recorder.dropped() == 6
    assert recorder.drain() == []


def test_engine_notes_its_programs_only_with_the_recorder_on():
    model, params = tiny_model("dense")
    spans.disable()
    eng = BucketedViTEngine(model, params, buckets=(2, 4)).warmup()
    assert spans.programs() == {}
    spans.enable()
    try:
        eng = BucketedViTEngine(model, params, buckets=(2, 4)).warmup()
        assert sorted(spans.programs()) == ["jit_fwd/2", "jit_fwd/4"]
        assert eng.trace_count == 2        # noting traced and compiled nothing
    finally:
        spans.disable()


@pytest.mark.parametrize("arm", ["shiftadd", "dense"])
def test_every_op_of_a_bucket_program_lies_under_a_named_scope(arm):
    model, params = tiny_model(arm)
    eng = BucketedViTEngine(model, params, buckets=(4,))
    x = np.zeros((4, TINY["image_size"], TINY["image_size"], 3), np.float32)
    text = eng._lower(x).compile().as_text()
    table = spans.entry_op_names(text)
    assert table
    outside = [name for name, op in table.items()
               if not set(TOP_SCOPES) & set(op.split("/"))]
    assert outside == []
    # Every scope reaches the HLO's metadata (fusion may hide a small one,
    # such as the combine, inside another scope's instruction).
    seen = {s for op in re.findall(r'op_name="([^"]*)"', text)
            for s in op.split("/")}
    assert set(TOP_SCOPES) <= seen
    assert set(MOE_SCOPES) & seen == (set(MOE_SCOPES) if arm == "shiftadd"
                                      else set())


def test_entry_table_takes_a_path_from_fused_ops_operands_or_users():
    text = "\n".join([
        "HloModule jit_fwd, entry_computation_layout={(f32[2])->f32[2]}",
        "",
        "%fused_computation (param_0: f32[2]) -> f32[2] {",
        '  %param_0 = f32[2]{0} parameter(0)',
        '  ROOT %add.1 = f32[2]{0} add(%param_0, %param_0), '
        'metadata={op_name="jit(fwd)/feed/moe_dispatch/add"}',
        "}",
        "",
        "ENTRY %main.9 (images.1: f32[2]) -> f32[2] {",
        '  %images.1 = f32[2]{0} parameter(0), metadata={op_name="images"}',
        '  %copy.1 = f32[2]{0} copy(%images.1), metadata={op_name="images"}',
        '  %mul.2 = f32[2]{0} multiply(%copy.1, %copy.1), '
        'metadata={op_name="jit(fwd)/patch_embed/mul"}',
        "  %fusion.3 = f32[2]{0} fusion(%mul.2), kind=kLoop, "
        "calls=%fused_computation",
        "  ROOT %copy.4 = f32[2]{0} copy(%fusion.3)",
        "}",
    ])
    assert spans.entry_op_names(text) == {
        "images.1": "jit(fwd)/patch_embed/mul",
        "copy.1": "jit(fwd)/patch_embed/mul",
        "mul.2": "jit(fwd)/patch_embed/mul",
        "fusion.3": "jit(fwd)/feed/moe_dispatch/add",
        "copy.4": "jit(fwd)/feed/moe_dispatch/add",
    }
