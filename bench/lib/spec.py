"""Finds a cell's pieces by name: `BENCHMARK.json`, the configuration file it
names, the model family `bench/families/<family>/` that the configuration
names, the traffic file `bench/traffic/<traffic>.json`, the per-layer metric
readers `bench/metrics/<metric>.py` and the peak table `bench/peaks.json`.

Nothing here knows a cell, a model family, a traffic mix or a metric by
name: a later change adds one by adding its files and its entries in
`BENCHMARK.json`.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = "bench"
# The files of a model family and what each has to define: make(cfg, seed)
# -> weights; build(cfg, weights) -> (model, params); forward(weights,
# images, cfg, dtype) -> logits and the keys of cfg it reads; the counts.
FAMILY_FILES = {
    "weights": ("make",),
    "program": ("build",),
    "reference": ("forward", "cache_keys"),
    "cost": ("ops_per_image", "kernel_calls"),
}


class SpecError(Exception):
    """A cell, configuration, model family, traffic mix, metric or device
    kind that the benchmark's files do not define, or a configuration that
    the program does not run as it states."""


@dataclasses.dataclass(frozen=True)
class Family:
    """The modules of `bench/families/<name>/`."""
    name: str
    weights: object
    program: object
    reference: object
    cost: object


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SpecError(f"no workload named {name!r} in BENCHMARK.json; have "
                    f"{[c['name'] for c in bench['workloads']]}")


def load_config(root: Path, bench: dict, name: str) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            return load_json(Path(root) / cfg["file"])
    raise SpecError(f"no configuration named {name!r} in BENCHMARK.json")


def load_traffic(root: Path, name: str) -> dict:
    path = Path(root) / BENCH_DIR / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no traffic file {path}")
    return load_json(path)


def _load_module(path: Path, module_name: str, names: tuple):
    """The Python file `path`, which has to define each of `names`."""
    if not path.is_file():
        raise SpecError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    for name in names:
        if not hasattr(module, name):
            raise SpecError(f"{path} defines no {name}")
    return module


def load_metric_reader(root: Path, name: str):
    """The module `bench/metrics/<name>.py`; its `read(ctx)` returns the
    metric's value, or None where the run has nothing to read."""
    path = Path(root) / BENCH_DIR / "metrics" / f"{name}.py"
    module = _load_module(path, f"bench_metric_{name}", ())
    if not callable(getattr(module, "read", None)):
        raise SpecError(f"{path} defines no read(ctx)")
    return module


def load_family(root: Path, cfg: dict) -> Family:
    """The model family that configuration `cfg` names by its key `family`:
    `weights.py`, `program.py`, `reference.py` and `cost.py` under
    `bench/families/<family>/`."""
    name = cfg.get("family")
    path = Path(root) / BENCH_DIR / "families" / str(name)
    if name is None or not path.is_dir():
        raise SpecError(f"configuration {cfg.get('name')!r} names the model "
                        f"family {name!r}, and there is no directory {path}")
    return Family(name, **{
        file: _load_module(path / f"{file}.py", f"bench_family_{name}_{file}",
                           names)
        for file, names in FAMILY_FILES.items()})


def load_peaks(root: Path, device_kind: str) -> dict:
    table = load_json(Path(root) / BENCH_DIR / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"device kind {device_kind!r} is not in the peak "
                        f"table; have {sorted(table['devices'])}")
    return table["devices"][device_kind]


def cell_metrics(bench: dict, cell_name: str, kind: str) -> list:
    """The `end_to_end` or `per_layer` metrics that this cell reports. A
    metric with a `workloads` list applies to those cells; one without it
    applies to every cell that reports the end-to-end metric it moves."""
    e2e_names = {m["name"] for m in bench["end_to_end"]
                 if cell_name in m.get("workloads", [cell_name])}
    if kind == "end_to_end":
        return [m for m in bench["end_to_end"] if m["name"] in e2e_names]
    return [m for m in bench["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e_names)]
