"""Whole runs of the harness on the CPU at a tiny size: the output check
passes on the program, and comes out false on the control and on the faults
a serving cell can have."""
import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.lib import reference, spec
from bench_fixtures import REPO, TINY

VIT = spec.load_family(REPO, {"family": "vit"})


def run_tiny(root, cell, impl="xla", **kw):
    return run.run_cell(root, cell, 11, 1.0, False, impl=impl,
                        t_process=time.perf_counter(), **kw)


CELLS = ["tiny-shiftadd.closed", "tiny-dense.closed"]


@pytest.mark.parametrize("cell", CELLS)
def test_program_passes_the_check_and_reports_the_cell_metrics(tiny_root, cell):
    res = run_tiny(tiny_root, cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"images_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["missing"] == {"value": 0.0, "limit": 0.0}


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_the_programs_place_fails_the_check(tiny_root, cell):
    res = run_tiny(tiny_root, cell, with_control=True)
    assert res["correct"]
    assert res["control_correct"] is False, res["control"]


def _warm_sizes(replicas):
    eng = replicas.engines[0]
    shape = (TINY["image_size"], TINY["image_size"], TINY["in_channels"])
    for n in range(1, eng.buckets[-1] + 1):
        np.asarray(eng.infer(np.zeros((n,) + shape, np.uint8)))


def altered_answer(replicas):
    """One logit of the first row of every batch altered where the engine
    produces it."""
    eng = replicas.engines[0]
    call = eng._call
    eng._call = lambda images: call(images).at[0, 0].add(1.0)
    _warm_sizes(replicas)


def half_batch_left_out(replicas):
    """Only the first half of each batch computed; the rest answered with
    the first half's logits."""
    eng = replicas.engines[0]
    call = eng._call

    def half(images):
        n = images.shape[0]
        if n == 1:
            return call(images) * 0.5
        out = call(images)[: n // 2]
        return jnp.concatenate([out, out[: n - n // 2]])
    eng._call = half
    _warm_sizes(replicas)


@pytest.mark.parametrize("fault", [altered_answer, half_batch_left_out])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_served_path_fails_the_check(tiny_root, cell, fault):
    res = run_tiny(tiny_root, cell, fault=fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("policy", ["dense", "shiftadd"])
def test_reference_matches_the_program_on_the_cpu(policy):
    from repro.core.policy import DENSE
    from repro.nn.vit import ShiftAddViT, ViTConfig
    from repro.serve.vision import build_policy_model

    cfg = dict(TINY, policy=policy)
    w = VIT.weights.make(cfg, 5)
    vcfg = ViTConfig(**{k: TINY[k] for k in (
        "image_size", "patch_size", "in_channels", "n_classes", "n_layers",
        "d_model", "n_heads", "d_ff")})
    dense = ShiftAddViT(dataclasses.replace(vcfg, policy=DENSE))
    model, params = build_policy_model(vcfg, policy, dense, w["dense"])
    if policy == "shiftadd":
        for i, blk in enumerate(params["blocks"]):
            blk["feed"]["router"] = {"kernel": w["router"][i]}
            blk["mixer"]["dwconv"] = w["dwconv"][i]
    images = np.random.default_rng(0).integers(0, 256, (6, 32, 32, 3),
                                               dtype=np.uint8)
    got = np.asarray(model.infer(params, jnp.asarray(images, jnp.float32),
                                 impl="xla"))
    want = reference.logits(VIT, w, images, cfg)
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


def test_weights_have_the_programs_dense_layout():
    from repro.core.policy import DENSE
    from repro.nn.vit import ShiftAddViT, ViTConfig

    cfg = json.loads((REPO / "bench/configs/deit-tiny-dense.json").read_text())
    vcfg = ViTConfig(**{k: cfg[k] for k in (
        "image_size", "patch_size", "in_channels", "n_classes", "n_layers",
        "d_model", "n_heads", "d_ff")}, policy=DENSE)
    prog = jax.eval_shape(ShiftAddViT(vcfg).init, jax.random.PRNGKey(0))
    ours = jax.eval_shape(lambda: VIT.weights.make(cfg, 0))["dense"]
    assert (jax.tree_util.tree_structure(prog)
            == jax.tree_util.tree_structure(ours))
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a.shape == b.shape and a.dtype == b.dtype, prog, ours))


def test_configuration_capacities_are_what_the_program_plans():
    from repro.core.policy import SHIFTADD
    from repro.nn.vit import ShiftAddViT, ViTConfig

    cfg = json.loads((REPO / "bench/configs/deit-tiny-shiftadd.json").read_text())
    vcfg = ViTConfig(**{k: cfg[k] for k in (
        "image_size", "patch_size", "in_channels", "n_classes", "n_layers",
        "d_model", "n_heads", "d_ff")}, policy=SHIFTADD,
        moe_capacity=cfg["moe_capacity_factor"])
    caps, _ = ShiftAddViT(vcfg).blocks[0].feed.capacity_plan(vcfg.n_patches)
    assert list(caps) == cfg["moe_capacity_per_image"]


def test_harness_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "deit-tiny-shiftadd.bulk", "--seed", str(2 ** 31 + 7), "--seconds",
         "1", "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())
    assert "needs 1 TPU chip" in proc.stderr


def test_traced_run_reports_its_window_and_leaves_out_what_it_cannot_read(
        tiny_root, capsys):
    # The CPU's trace has no TPU plane, so the device reader finds nothing:
    # the metric is left out and said so, and the traced window is the
    # second half of a short run.
    res = run.run_cell(tiny_root, "tiny-shiftadd.closed", 11, 1.0, True,
                       impl="xla", t_process=time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["metrics"] == {}
    assert 0.45 < res["device"]["window_s"] < 0.6
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "metric idle_share.bulk: its reader found nothing" in (
        capsys.readouterr().err)
