"""The benchmark finds every piece of a cell by name, in its own files."""
import json

import pytest

from bench.lib import spec
from bench_fixtures import REPO

BENCH = spec.load_benchmark(REPO)


@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_every_cell_loads_its_configuration_traffic_and_metrics(cell):
    c = spec.find_cell(BENCH, cell)
    cfg = spec.load_config(REPO, BENCH, c["config"])
    traffic = spec.load_traffic(REPO, c["traffic"])
    assert cfg["name"] == c["config"]
    assert set(traffic["serve"]) == {"buckets", "replicas", "calibrate_iters"}
    e2e = {m["name"] for m in spec.cell_metrics(BENCH, cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = spec.cell_metrics(BENCH, cell, "per_layer")
    assert layer
    for m in layer:
        assert callable(spec.load_metric_reader(REPO, m["name"]).read)


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_every_configuration_file_states_what_is_run(cfg):
    body = spec.load_config(REPO, BENCH, cfg["name"])
    assert cfg["file"].startswith("bench/configs/")
    assert body["source"] == cfg["source"]
    assert body["policy"] in ("dense", "shiftadd")
    assert set(body["limits"]) and all(v > 0 for v in body["limits"].values())


def test_benchmark_names_and_units_keep_to_the_contract():
    import re
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for entry in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert name.match(entry["name"]), entry["name"]
    for m in metrics:
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_a_new_traffic_file_and_metric_file_are_found_by_name(tiny_root):
    (tiny_root / "bench" / "traffic" / "burst.json").write_text(
        json.dumps({"loop": "open", "rate_rps": 5}))
    (tiny_root / "bench" / "metrics" / "answer.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    assert spec.load_traffic(tiny_root, "burst")["rate_rps"] == 5
    assert spec.load_metric_reader(tiny_root, "answer").read(None) == 42.0
    with pytest.raises(spec.SpecError):
        spec.load_traffic(tiny_root, "no-such-mix")
    with pytest.raises(spec.SpecError):
        spec.load_metric_reader(tiny_root, "no_such_metric")


def test_peak_table_knows_the_v5e_and_refuses_an_unknown_kind():
    peaks = spec.load_peaks(REPO, "TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["int8_ops_per_s"] == 393e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.load_peaks(REPO, "TPU v9 imaginary")


def test_a_metric_without_workloads_reaches_every_cell_of_its_metric():
    bench = {"end_to_end": [{"name": "e", "workloads": ["a"]},
                            {"name": "setup_s"}],
             "per_layer": [{"name": "x", "moves": "e"},
                           {"name": "y", "moves": "e", "workloads": ["b"]}]}
    assert [m["name"] for m in spec.cell_metrics(bench, "a", "per_layer")] == ["x"]
    assert [m["name"] for m in spec.cell_metrics(bench, "b", "per_layer")] == ["y"]
