"""Reduction of profiler traces to device metrics."""
import json

import pytest

from bench.lib import cost, spec, trace
from bench.lib.context import Context, kernel_roofline
from bench.lib.trace import Event, Trace
from bench_fixtures import REPO

DEV = "/device:TPU:0"
VIT = spec.load_family(REPO, {"family": "vit"})


def small_trace():
    ops = [Event("fusion.1", 0.1, 0.2), Event("fusion.7", 0.2, 0.2),
           Event("shift_matmul_pallas.3", 0.6, 0.1),
           Event("copy.2", 1.2, 0.5)]                 # after the window
    spans = [Event("wait", 0.0, 1.0), Event("submit", 0.45, 0.1)]
    return Trace((0.0, 1.0), {DEV: ops}, spans)


def test_busy_and_idle_are_the_union_of_device_ops_in_the_window():
    tr = small_trace()
    assert tr.busy_s() == pytest.approx(0.4)
    assert tr.idle_share() == pytest.approx(0.6)
    assert tr.window_s == 1.0


def test_idle_gaps_are_labelled_by_the_host_span_open_in_them():
    gaps = small_trace().idle_gaps()
    assert [g[0] for g in gaps] == ["wait", "submit", "wait"]
    assert [g[1] for g in gaps] == pytest.approx([0.3, 0.2, 0.1])


def test_top_ops_group_instruction_numbers():
    top = small_trace().top_ops()
    assert top[0] == ["fusion", pytest.approx(0.4)]
    assert top[1] == ["shift_matmul_pallas", pytest.approx(0.1)]


def test_kernel_roofline_counts_calls_per_forward():
    cfg = {"image_size": 224, "patch_size": 16, "d_model": 192, "d_ff": 768,
           "n_layers": 1, "moe_experts": ["mult", "shift"],
           "moe_capacity_per_image": [108, 138]}
    calls = VIT.cost.shift_matmul_calls(cfg, 32)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    roof = sum(cost.roofline_s(*cost.shift_matmul_cost(*c), 197e12, 819e9)
               for c in calls)
    ops = [Event(f"shift_matmul_pallas.{i}", 0.001 * i, roof / len(calls) * 4)
           for i in range(2 * len(calls))]                 # two forwards
    tr = Trace((0.0, 1.0), {DEV: ops}, [])

    class Window:
        batches = [(32, 32, "fill", 0.0, 0.1)] * 5
    ctx = Context(cfg=cfg, window=Window(), trace=tr, peaks=peaks,
                  images_per_s=1.0)
    share = kernel_roofline(ctx, r"^shift_matmul_pallas(\.\d+)?$",
                            VIT.cost.shift_matmul_calls, cost.shift_matmul_cost)
    assert share == pytest.approx(25.0)
    assert kernel_roofline(ctx, r"^no_such_kernel$",
                           VIT.cost.shift_matmul_calls,
                           cost.shift_matmul_cost) is None


def test_find_xplane_refuses_a_directory_without_a_trace(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace.find_xplane(str(tmp_path))


DATA = __import__("pathlib").Path(__file__).parent / "data"
CHIP_TRACE = DATA / "shiftadd-bucket32.xplane.pb"


# The trace's session start and the slice's length, in wall-clock ns.
START_NS, SLICE_NS = 1792244311008877222, 15_015_212


def chip_trace(spans=()):
    # A device trace of deit-tiny-shiftadd.bulk on one TPU v5 lite, cut to
    # 15 ms around one engine call at bucket 32 (the input's conversion,
    # 2 ms after the slice's start, and one forward, ending 2 ms before its
    # end), the session start moved to the slice's start, and event names
    # cut to 80 characters. The whole slice is the traced window.
    return trace.load(str(CHIP_TRACE), (START_NS, START_NS + SLICE_NS),
                      spans)


def test_load_names_device_ops_by_their_hlo_instruction():
    assert trace.instruction_name(
        "%fusion.84 = s32[7872]{0:T(1024)S(1)} fusion(s32[32,196]{1,0}") == (
        "fusion.84")
    tr = chip_trace()
    names = {e.name for e in tr.device_events()}
    assert "shift_matmul_pallas.74" in names
    assert not any(" " in n or n.startswith("%") for n in names)


def test_kernel_events_of_one_forward_are_its_calls():
    cfg = json.loads((REPO / "bench/configs/deit-tiny-shiftadd.json").read_text())
    tr = chip_trace()
    calls = VIT.cost.kernel_calls(cfg, 32)
    for metric, kernel in (("shift_matmul_roofline", "shift_matmul"),
                           ("bidir_attn_roofline", "bidir_attn")):
        reader = spec.load_metric_reader(REPO, metric)
        assert len(tr.kernel_events(reader.PATTERN)) == len(calls[kernel])


def test_readers_on_a_chip_trace_read_shares_below_the_whole():
    cfg = json.loads((REPO / "bench/configs/deit-tiny-shiftadd.json").read_text())

    class Window:
        batches = [(32, 32, "full", 0.0, 0.1)]
    ctx = Context(cfg=cfg, window=Window(), trace=chip_trace(),
                  peaks=spec.load_peaks(REPO, "TPU v5 lite"), images_per_s=1.0,
                  family=spec.load_family(REPO, cfg))
    for metric in ("shift_matmul_roofline", "bidir_attn_roofline",
                   "idle_share.bulk"):
        value = spec.load_metric_reader(REPO, metric).read(ctx)
        assert 0.0 < value < 100.0, (metric, value)


def test_busy_time_of_a_chip_trace_is_the_time_of_its_programs():
    # The union of the device ops equals the summed time of the two XLA
    # programs in the slice (the conversion and the forward), read from the
    # trace's own "XLA Modules" line.
    from jax.profiler import ProfileData

    modules = [e.duration_ns * 1e-9
               for plane in ProfileData.from_file(str(CHIP_TRACE)).planes
               if plane.name == "/device:TPU:0"
               for line in plane.lines if line.name == "XLA Modules"
               for e in line.events]
    tr = chip_trace()
    assert len(modules) == 2
    assert tr.busy_s() == pytest.approx(sum(modules), rel=1e-3)
    assert tr.window_s == pytest.approx(0.015, rel=0.01)
    assert tr.top_ops()[1][0] == "shift_matmul_pallas"


def test_host_spans_on_the_wall_clock_label_the_gaps_of_a_chip_trace():
    # The 2 ms before the conversion fall in `submit`, the 2 ms after the
    # forward in `wait`: the spans are placed by the session's start.
    ms = 1_000_000
    tr = chip_trace([("submit", START_NS, START_NS + 2 * ms),
                     ("wait", START_NS + 12 * ms, START_NS + SLICE_NS)])
    labels = [name for name, dur in tr.idle_gaps() if dur > 0.0019]
    assert sorted(labels) == ["submit", "wait"]
