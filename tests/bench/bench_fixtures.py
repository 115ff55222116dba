"""Shared pieces of the benchmark's tests: a throwaway checkout root holding
tiny configurations, so that a whole run fits a CPU test."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

# Widths of a ViT small enough for the CPU: 16 patches of 8x8x3, 2 layers.
TINY = dict(image_size=32, patch_size=8, in_channels=3, n_classes=10,
            n_layers=2, d_model=64, n_heads=2, d_ff=128,
            activation_dtype="float32", moe_experts=["mult", "shift"],
            moe_capacity_factor=1.25, moe_capacity_per_image=[8, 13])
# On the CPU the program and the reference agree to about 5e-7 at this size
# and the bfloat16 control reads about 0.2.
TINY_LIMITS = {"logit_err_highest": 1e-4, "logit_err_default": 1e-4}

CLOSED = dict(loop="closed", clients=4, sizes={"kind": "fixed", "value": 8},
              classes={"relaxed": {"share": 1.0, "budget_ms": 10000}},
              payload_pool=64, check_requests=8,
              serve={"buckets": [8], "replicas": 2, "calibrate_iters": 1})

def make_root(path: Path) -> Path:
    """A checkout root with BENCHMARK.json, two tiny configurations, a
    closed-loop traffic mix, and the repository's model families and metric
    readers."""
    (path / "bench" / "configs").mkdir(parents=True)
    (path / "bench" / "traffic").mkdir(parents=True)
    for part in ("families", "metrics"):
        shutil.copytree(REPO / "bench" / part, path / "bench" / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "bench" / "peaks.json", path / "bench" / "peaks.json")
    configs, cells = [], []
    for arm, stage in (("shiftadd", 2), ("dense", 0)):
        cfg = dict(TINY, name=f"tiny-{arm}", family="vit", policy=arm,
                   convert_stage=stage, limits=TINY_LIMITS)
        file = f"bench/configs/tiny-{arm}.json"
        (path / file).write_text(json.dumps(cfg))
        configs.append({"name": cfg["name"], "file": file})
        cells.append({"name": f"tiny-{arm}.closed", "config": cfg["name"],
                      "traffic": "closed", "chips": 1})
    (path / "bench" / "traffic" / "closed.json").write_text(json.dumps(CLOSED))
    bench = {
        "configs": configs, "workloads": cells,
        "end_to_end": [
            {"name": "images_per_s", "unit": "images/s"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "idle_share.bulk", "unit": "%", "moves": "images_per_s"}],
    }
    (path / "BENCHMARK.json").write_text(json.dumps(bench))
    return path
