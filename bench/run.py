"""Runs one benchmark cell once on the chip and prints its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (`BENCHMARK.json`) names a configuration file and a traffic file;
the configuration names its model family, `bench/families/<family>/`. From
the family, set-up makes the weights from the seed (`weights.make`) and the
program's model and parameters (`program.build`); it then builds the
program's serving path over them (`make_replicas(arm="thread")`, frozen
bucket programs with `impl="pallas"`), warms every shape the traffic will
use and calibrates the scheduler. The window then drives the program's
`MicroBatchScheduler` and replicas on the wall clock for `--seconds`
(`bench/lib/serve_loop.py`). After it, the program is freed and the
family's plain reference (`reference.forward`, through
`bench/lib/reference.py`) recomputes the checked requests.

`--trace 0` reports the cell's end-to-end metrics, `--trace 1` its
per-layer metrics, read from a device trace of the window's last seconds,
the benchmark's host spans and the window's timestamps. The last line of
standard output is one JSON object; the last lines of standard error give
each number compared with its limit. The run exits non-zero, with no result
line, where JAX finds no TPU or fewer chips than the cell asks for, and
where anything compiles inside the window.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# Caches and traces of the benchmark, inside the checkout at fixed paths.
WORK = ROOT / ".bench_work"
# The traced part of a --trace 1 window: its last TRACE_S seconds, at most
# half of it, so that the part before it runs with the profiler off.
TRACE_S = 4.0


class BenchError(Exception):
    """A run that must end without a result line."""


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def setup_process():
    """Compile cache, libtpu logs and import paths: set before JAX loads."""
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    cache = WORK / "jax_cache"
    # The program takes its cache directory from this variable.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    if "TPU_LOG_DIR" not in os.environ:
        (WORK / "tpu_logs").mkdir(parents=True, exist_ok=True)
        os.environ["TPU_LOG_DIR"] = str(WORK / "tpu_logs")
    import jax

    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts JAX's compile requests, persistent-cache hits and misses, and
    backend compiles, through `jax.monitoring`."""

    KEYS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
            "/jax/compilation_cache/cache_hits": "cache_hits",
            "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax
        from jax._src import dispatch

        self.counts = {"requests": 0, "cache_hits": 0, "cache_misses": 0,
                       "backend_compiles": 0}
        self._compile_event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name in self.KEYS:
            self.counts[self.KEYS[name]] += 1

    def _duration(self, name, _secs, **_):
        if name == self._compile_event:
            self.counts["backend_compiles"] += 1

    def snapshot(self):
        return dict(self.counts)


def build_program(family, cfg, weights, traffic, impl):
    """The program's serving path for this configuration and traffic."""
    from repro.serve.replicas import make_replicas

    model, params = family.program.build(cfg, weights)
    serve = traffic["serve"]
    return make_replicas(model, params, n_replicas=serve["replicas"],
                         arm="thread", buckets=tuple(serve["buckets"]),
                         impl=impl)


def warm(replicas, cfg, traffic):
    """Compile every bucket, then serve once every batch size the traffic
    can form, so that the eager padding and slicing ops around the bucket
    programs compile now and not in the window. Returns the scheduler's
    service model (the program's own calibration)."""
    import numpy as np

    from bench.lib.traffic import request_size
    from repro.serve.frontend import calibrate_service_models

    replicas.warmup()
    shape = (cfg["image_size"], cfg["image_size"], cfg["in_channels"])
    engine = replicas.engines[0]
    largest = engine.buckets[-1]
    batch_sizes = ([largest] if request_size(traffic["sizes"]) == largest
                   else range(1, largest + 1))
    for n in batch_sizes:
        np.asarray(engine.infer(np.zeros((n,) + shape, np.uint8)))
    iters = traffic["serve"]["calibrate_iters"]
    return calibrate_service_models([replicas], shape, iters=iters)[0]


class Tracer(threading.Thread):
    """Profiles the window from `delay` seconds after it starts, for
    `seconds`. `t_on` (perf_counter) is when profiling began; `window_ns`
    (wall clock, ns) the traced part, inside the profiler's session."""

    def __init__(self, trace_dir, delay, seconds):
        super().__init__(name="bench-trace")
        self.trace_dir, self.delay, self.seconds = trace_dir, delay, seconds
        self.t_on = None
        self.window_ns = None

    def run(self):
        import jax

        # Device tracing only. The host tracer, at any level that keeps the
        # benchmark's annotations, records about a million runtime events a
        # second and slowed the shiftadd cell from about 350 forwards in 4 s
        # to 37-55; the benchmark keeps its own host spans (trace.SpanLog).
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 0
        options.python_tracer_level = 0
        time.sleep(self.delay)
        self.t_on = time.perf_counter()
        jax.profiler.start_trace(str(self.trace_dir),
                                 profiler_options=options)
        t0 = time.time_ns()
        time.sleep(self.seconds)
        self.window_ns = (t0, time.time_ns())
        jax.profiler.stop_trace()


def spanned(fn, span, name):
    def wrapper(*a, **kw):
        with span(name):
            return fn(*a, **kw)
    return wrapper


def e2e_metrics(window, setup_s):
    """The end-to-end metrics the benchmark takes on the host clock."""
    import numpy as np

    lat = window.latencies_s()
    return {
        "images_per_s": ("images/s", window.images_in_window() / window.seconds),
        "setup_s": ("s", setup_s),
    }, {"requests": len(lat), "completed": int(np.isfinite(lat).sum())}


def run_cell(root, cell_name, seed, seconds, trace, *, impl="pallas",
             t_process=None, fault=None, with_control=False, keep_trace=False):
    """One run of a cell; returns the result object. `fault(replicas)` may
    break the served path (tests). `with_control` adds the key `control`:
    the numbers of the control, the reference in bfloat16, in the program's
    place on the same images, and `control_correct`, the verdict of the
    cell's limits on them. `keep_trace` leaves the profiler's files under
    `.bench_work/trace/<cell>`."""
    import jax
    import numpy as np

    from bench.lib import check, reference, spec
    from bench.lib.context import Context
    from bench.lib.serve_loop import run_window
    from bench.lib.traffic import ClosedClients, payload_indices, payload_pool
    from repro.serve.scheduler import MicroBatchScheduler

    t_process = T_PROCESS if t_process is None else t_process
    bench = spec.load_benchmark(root)
    cell = spec.find_cell(bench, cell_name)
    cfg = spec.load_config(root, bench, cell["config"])
    family = spec.load_family(root, cfg)
    traffic = spec.load_traffic(root, cell["traffic"])
    kind = "per_layer" if trace else "end_to_end"
    wanted = spec.cell_metrics(bench, cell_name, kind)
    dev = jax.devices()[0]
    peaks = spec.load_peaks(root, dev.device_kind) if dev.platform == "tpu" else None
    readers = {m["name"]: spec.load_metric_reader(root, m["name"])
               for m in wanted} if trace else {}

    compiles = CompileCounter()
    weights = family.weights.make(cfg, seed)
    replicas = build_program(family, cfg, weights, traffic, impl)
    del weights
    engine = replicas.engines[0]
    service = warm(replicas, cfg, traffic)
    scheduler = MicroBatchScheduler(replicas.buckets, service)
    shape = (cfg["image_size"], cfg["image_size"], cfg["in_channels"])
    pool = payload_pool(traffic, shape, seed)
    clients = ClosedClients(traffic, seed)
    if fault is not None:
        fault(replicas)
    log(f"setup: {compiles.snapshot()} (compile requests, persistent-cache "
        f"hits and misses, backend compiles); service model s {service}")

    span, tracer, trace_dir = None, None, None
    if trace:
        from bench.lib.trace import SpanLog

        trace_dir = WORK / "trace" / cell_name
        shutil.rmtree(trace_dir, ignore_errors=True)
        span = SpanLog()
        engine.infer = spanned(engine.infer, span, "engine_call")
    counters0 = (engine.images_served, engine.padded_images_served)
    traces0 = engine.trace_count
    compiles0 = compiles.snapshot()
    if trace:
        traced = min(TRACE_S, seconds / 2)
        tracer = Tracer(trace_dir, seconds - traced, traced)
        tracer.start()
    setup_s = time.perf_counter() - t_process
    window = run_window(replicas, scheduler, pool, clients, seconds,
                        span=span)
    if tracer is not None:
        tracer.join()
    in_window = {k: compiles.snapshot()[k] - compiles0[k] for k in compiles0}
    retraces = engine.trace_count - traces0
    counters = {"images": engine.images_served - counters0[0],
                "padded_images": engine.padded_images_served - counters0[1]}
    stats = (dev.memory_stats() or {}) if dev.platform == "tpu" else {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    if retraces or any(in_window.values()):
        raise BenchError(f"compiled inside the window: {retraces} engine "
                         f"retraces, {in_window}")

    checked = [r for r in window.requests if r.arrival.check
               and clients.checked(r.arrival.client, r.arrival.index)]
    served = []
    for r in checked:
        rows = r.logits() if np.isfinite(r.done) and r.parts else None
        served.extend(list(rows) if rows is not None
                      else [None] * r.arrival.size)
    images = np.concatenate([
        pool[payload_indices(r.arrival.img_offset, 0, r.arrival.size,
                             len(pool))] for r in checked])
    replicas.close()
    del replicas, engine, scheduler
    gc.collect()

    t_ref = time.perf_counter()
    weights = family.weights.make(cfg, seed)
    refs = {p: reference.logits(family, weights, images, cfg, precision=p)
            for p in ("highest", "default")}
    numbers = check.compare(served, refs, cfg["n_classes"])
    correct, checks = check.judge(numbers, cfg["limits"])
    ref_s = time.perf_counter() - t_ref
    log("numbers: " + " ".join(f"{k} {v:.6g}" for k, v in numbers.items()))
    if with_control:
        ctl = reference.logits(family, weights, images, cfg,
                               dtype="bfloat16")
        control_numbers = check.compare(list(ctl), refs, cfg["n_classes"])
        control_correct = check.judge(control_numbers, cfg["limits"])[0]

    e2e, counts = e2e_metrics(window, setup_s)
    metrics = {}
    if trace:
        from bench.lib.trace import find_xplane, load

        tr = load(find_xplane(str(trace_dir)), tracer.window_ns, span.spans)
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = Context(cfg=cfg, window=window, trace=tr, peaks=peaks,
                      images_per_s=window.images_per_s_until(tracer.t_on),
                      family=family)
        for m in wanted:
            value = readers[m["name"]].read(ctx)
            if value is None:
                log(f"metric {m['name']}: its reader found nothing to read "
                    f"in this run; it is left out of the result line")
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device_extra = {"busy_s": tr.busy_s(), "window_s": tr.window_s}
        breakdown = {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}
    else:
        for m in wanted:
            metrics[m["name"]] = {"value": e2e[m["name"]][1], "unit": m["unit"]}
        device_extra, breakdown = {}, None

    lat = window.lateness_s()
    log(f"window: {counts['requests']} requests due, {counts['completed']} "
        f"completed, {window.images_in_window()} images completed in "
        f"{window.seconds:.3f} s, {len(window.batches)} batches; generator "
        f"lateness p50 {1e3 * np.median(lat):.3f} ms, max "
        f"{1e3 * lat.max():.3f} ms; counters {counters}; reference "
        f"{ref_s:.2f} s over {len(images)} images")
    for name, c in checks.items():
        log(f"check {name} {c['value']:.6g} limit {c['limit']}")
    result = {
        "correct": bool(correct),
        "attempted": counts["requests"],
        "failed": counts["requests"] - counts["completed"],
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": memory_peak, **device_extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    if with_control:
        result["numbers"] = numbers
        result["control"] = control_numbers
        result["control_correct"] = control_correct
    result["checks"] = checks
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    setup_process()
    import jax

    from bench.lib import spec

    cell = spec.find_cell(spec.load_benchmark(ROOT), args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"bench: needs {cell['chips']} TPU chip(s); JAX has "
            f"{len(devices)} device(s) of platform {devices[0].platform!r}")
        return 1
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except (BenchError, spec.SpecError) as e:
        log(f"bench: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
