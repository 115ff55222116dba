"""The readers of the program's own spans and named scopes
(`bench/lib/program_spans.py` and the metric files that use it), on
synthetic traces, in a whole run on the CPU, and on a program without the
recorder."""
import json
import time
import types

import pytest

from bench import run
from bench.lib import program_spans, spec
from bench.lib.context import Context
from bench.lib.trace import Event, Trace
from bench_fixtures import REPO
from repro.serve.spans import Span

DEV = "/device:TPU:0"
START_NS = 1_800_000_000_000_000_000        # the session's start, wall ns
NEW_METRICS = ["engine_host_ms", "replica_wait_ms", "idle_in_engine.bulk",
               "mixer_ms", "feed_ms", "moe_dispatch_ms"]


def ns(t):
    return START_NS + int(round(t * 1e9))


def batch_records(batch, t, thread="vit-replica_0"):
    """The spans of one batch submitted at t (s): 1 ms queued, then 1 ms of
    engine host work and 3 ms waiting on the device."""
    rec = []
    for name, a, b, parent in (
            ("replica.queued", 0.000, 0.001, None),
            ("replica.run", 0.001, 0.006, None),
            ("engine.put", 0.0011, 0.0015, "replica.run"),
            ("engine.enqueue", 0.0015, 0.0019, "replica.run"),
            ("engine.slice", 0.0019, 0.0021, "replica.run"),
            ("replica.device_wait", 0.0021, 0.006, "replica.run")):
        rec.append(Span(name, ns(t + a), ns(t + b), thread, parent, batch,
                        32 if name.startswith("engine") else None, 32))
    return rec


class Window:
    batches = [(32, 32, "full", 0.0, 0.1)] * 4


TABLE = {"fusion.1": "jit(fwd)/patch_embed/dot_general",
         "fusion.2": "jit(fwd)/mixer/add",
         "shift_matmul_pallas.3": "jit(fwd)/mixer/jit(shift_matmul_pallas)/x",
         "sort.4": "jit(fwd)/feed/moe_dispatch/sort",
         "fusion.5": "jit(fwd)/feed/expert_shift/dot_general",
         "gather.6": "jit(fwd)/feed/moe_combine/gather",
         "fusion.7": "jit(fwd)/head/reduce_sum"}


def one_forward(t0):
    """The ops of one forward from t0, 1 ms each, in the table's order."""
    return [Event(name, t0 + 0.001 * i, 0.001)
            for i, name in enumerate(TABLE)]


@pytest.fixture
def recorder(monkeypatch):
    """The program's recorder replaced by one that holds given records."""
    held = {"records": [], "programs": {}}
    fake = types.SimpleNamespace(
        drain=lambda: held.pop("records", []),
        programs=lambda: held["programs"],
        enabled=lambda: True, enable=lambda: None)
    monkeypatch.setattr(program_spans, "_spans", fake)
    monkeypatch.setitem(program_spans._cache, "ctx", None)
    return held


def context(records, recorder, ops=(), engine_calls=None):
    """A Context whose trace holds `ops` and the benchmark's engine_call
    spans, which open 5 us before each engine.put."""
    recorder["records"] = list(records)
    recorder["programs"] = {"jit_fwd/32": TABLE}
    if engine_calls is None:
        engine_calls = [Event("engine_call", (r.t0_ns - START_NS) * 1e-9
                              - 5e-6, 0.001)
                        for r in records if r.name == "engine.put"]
    tr = Trace((0.0, 0.1), {DEV: list(ops)}, list(engine_calls))
    return Context(cfg={}, window=Window(), trace=tr, peaks=None,
                   images_per_s=1.0)


def test_engine_and_replica_spans_are_placed_and_read_per_batch(recorder):
    # Two batches in the window, one before it (the warm-up: no
    # benchmark span) and the device busy 2 ms of each batch's 5.
    recs = (batch_records(None, -0.05) + batch_records(0, 0.010)
            + batch_records(1, 0.050, "vit-replica_1"))
    calls = [Event("engine_call", 0.0111 - 5e-6, 0.001),
             Event("engine_call", 0.0511 - 5e-6, 0.001)]
    ops = [Event("fusion.2", 0.0125, 0.002), Event("fusion.2", 0.0525, 0.002)]
    ctx = context(recs, recorder, ops, calls)
    placed = program_spans.placed(ctx)
    put = [s for s in placed if s[0] == "engine.put" and s[3] == 0][0]
    assert put[1] == pytest.approx(0.0111, abs=1e-5)
    assert program_spans.placed(ctx) is placed          # drained once a run
    read = {m: spec.load_metric_reader(REPO, m).read(ctx)
            for m in ("engine_host_ms", "replica_wait_ms",
                      "idle_in_engine.bulk")}
    assert read["engine_host_ms"] == pytest.approx(1.0, rel=1e-3)
    assert read["replica_wait_ms"] == pytest.approx(1.0, rel=1e-3)
    # Each batch's engine spans cover 1 ms in which the device is idle: 2 ms
    # of the 100-ms window.
    assert read["idle_in_engine.bulk"] == pytest.approx(2.0, rel=1e-2)


def test_idle_in_engine_leaves_out_the_device_busy_part(recorder):
    recs = batch_records(0, 0.010)
    ops = [Event("fusion.2", 0.0115, 0.0005)]            # inside engine.put
    ctx = context(recs, recorder, ops)
    value = spec.load_metric_reader(REPO, "idle_in_engine.bulk").read(ctx)
    assert value == pytest.approx(100 * (0.001 - 0.0005) / 0.1, rel=1e-2)


def test_spans_that_do_not_align_with_the_benchmarks_are_not_placed(recorder):
    recs = [r for t in range(6) for r in batch_records(t, 0.010 * (t + 1))]
    calls = [Event("engine_call", 0.003 * i * i, 0.001) for i in range(6)]
    ctx = context(recs, recorder, engine_calls=calls)
    assert program_spans.placed(ctx) is None
    assert spec.load_metric_reader(REPO, "engine_host_ms").read(ctx) is None


def test_scope_readers_give_device_time_per_forward(recorder):
    ops = (one_forward(0.010) + one_forward(0.030) + one_forward(0.050)
           + [Event("copy.99", 0.070, 0.005)])       # another program's op
    ctx = context(batch_records(0, 0.005), recorder, ops)
    read = {m: spec.load_metric_reader(REPO, m).read(ctx)
            for m in ("mixer_ms", "feed_ms", "moe_dispatch_ms")}
    assert read == pytest.approx({"mixer_ms": 2.0, "feed_ms": 3.0,
                                  "moe_dispatch_ms": 2.0})


def test_scope_reader_finds_nothing_where_no_op_has_the_scope(recorder):
    ctx = context([], recorder, one_forward(0.010))
    recorder["programs"] = {"jit_fwd/32": {
        k: v for k, v in TABLE.items() if "moe_" not in v}}
    assert spec.load_metric_reader(REPO, "moe_dispatch_ms").read(ctx) is None
    assert spec.load_metric_reader(REPO, "mixer_ms").read(ctx) == (
        pytest.approx(2.0))


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_program_without_the_recorder_gives_nothing_to_read(monkeypatch,
                                                              metric):
    monkeypatch.setattr(program_spans, "_spans", None)
    reader = spec.load_metric_reader(REPO, metric)
    tr = Trace((0.0, 1.0), {DEV: one_forward(0.1)},
               [Event("engine_call", 0.1, 0.001)])
    ctx = Context(cfg={}, window=Window(), trace=tr, peaks=None,
                  images_per_s=1.0)
    assert reader.read(ctx) is None


def test_loading_a_reader_turns_the_programs_recorder_on():
    from repro.serve import spans

    spans.disable()
    spec.load_metric_reader(REPO, "engine_host_ms")
    assert spans.enabled()
    spans.disable()


def test_a_traced_run_on_the_cpu_reads_the_programs_host_spans(tiny_root,
                                                                capsys):
    # The CPU's trace has no TPU plane: the host-span metrics are read, the
    # device-side ones are left out.
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["per_layer"] += [{"name": m, "unit": "ms", "moves": "images_per_s"}
                           for m in NEW_METRICS]
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = run.run_cell(tiny_root, "tiny-shiftadd.closed", 11, 1.0, True,
                       impl="xla", t_process=time.perf_counter())
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"engine_host_ms", "replica_wait_ms"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    err = capsys.readouterr().err
    for m in ("idle_in_engine.bulk", "mixer_ms", "feed_ms", "moe_dispatch_ms"):
        assert f"metric {m}: its reader found nothing" in err


# Two cuts of device traces of the bulk cells on one TPU v5 lite, from
# traced runs with the program's recorder on that kept the profiler's files
# (`run_cell(keep_trace=True)`, as bench/tools/trace_sample.py runs it):
# the session start moved to the cut's start, the TPU plane's module and op
# lines kept with names cut to 80 characters, events wholly inside the cut.
# Beside each, the program's records and the benchmark's spans that lie
# wholly inside it (wall-clock ns) and the bucket program's scope table for
# the instructions in it. `dense-...`: 8 ms around one batch's engine call,
# its input's conversion and its forward; `shiftadd-...`: one whole
# forward, 5 us either side.
DATA = REPO / "tests" / "bench" / "data"
CUTS = ["dense-bucket32-spans", "shiftadd-bucket32-spans"]


def chip_cut(name, recorder):
    from bench.lib import trace

    fx = json.loads((DATA / f"{name}.json").read_text())
    start = fx["start_ns"]
    tr = trace.load(str(DATA / f"{name}.xplane.pb"),
                    (start, start + fx["slice_ns"]),
                    [tuple(s) for s in fx["bench_spans"]])
    recorder["records"] = [Span(*r) for r in fx["records"]]
    recorder["programs"] = {"jit_fwd/32": fx["table"]}
    ctx = Context(cfg={}, window=Window(), trace=tr, peaks=None,
                  images_per_s=1.0)
    return fx, ctx


def chip_modules(name):
    from jax.profiler import ProfileData

    return [(e.name.split("(")[0], e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for plane in ProfileData.from_file(
                str(DATA / f"{name}.xplane.pb")).planes
            if plane.name == DEV
            for line in plane.lines if line.name == "XLA Modules"
            for e in line.events]


@pytest.mark.parametrize("cut", CUTS)
def test_program_spans_land_where_the_session_start_puts_them(recorder, cut):
    fx, ctx = chip_cut(cut, recorder)
    placed = program_spans.placed(ctx)
    truth = [(r[1] - fx["start_ns"]) * 1e-9 for r in fx["records"]]
    assert len(placed) == len(truth)
    assert max(abs(p[1] - t) for p, t in zip(placed, truth)) < 2e-5


@pytest.mark.parametrize("cut", CUTS)
def test_scopes_of_a_chip_forward_cover_its_device_time(recorder, cut):
    _, ctx = chip_cut(cut, recorder)
    fwd = [dur for name, _, dur in chip_modules(cut) if name == "jit_fwd"]
    assert len(fwd) == 1
    scoped = program_spans.scope_ms(ctx, ("patch_embed", "mixer", "feed",
                                          "head"))
    assert scoped == pytest.approx(1e3 * fwd[0], rel=2e-3)
    read = {m: spec.load_metric_reader(REPO, m).read(ctx)
            for m in ("mixer_ms", "feed_ms", "moe_dispatch_ms")}
    assert 0 < read["mixer_ms"] and 0 < read["feed_ms"]
    assert read["mixer_ms"] + read["feed_ms"] < scoped
    if cut.startswith("shiftadd"):
        assert 0 < read["moe_dispatch_ms"] < read["feed_ms"]
    else:
        assert read["moe_dispatch_ms"] is None


def test_the_dense_engines_host_work_holds_the_device_idle(recorder):
    # In the dense cut the device is idle through the batch's engine spans:
    # its conversion and forward wait for the input's transfer.
    fx, ctx = chip_cut("dense-bucket32-spans", recorder)
    ms = {r[0]: (r[2] - r[1]) * 1e-6 for r in fx["records"]}
    engine_ms = ms["engine.put"] + ms["engine.enqueue"] + ms["engine.slice"]
    window_ms = fx["slice_ns"] * 1e-6
    read = {m: spec.load_metric_reader(REPO, m).read(ctx)
            for m in ("engine_host_ms", "replica_wait_ms",
                      "idle_in_engine.bulk")}
    assert read["engine_host_ms"] == pytest.approx(engine_ms)
    assert read["replica_wait_ms"] == pytest.approx(ms["replica.queued"])
    assert read["idle_in_engine.bulk"] == pytest.approx(
        100 * engine_ms / window_ms)
