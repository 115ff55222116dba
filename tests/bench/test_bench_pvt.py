"""The pvt family: its counts against counts made by hand, the reduced
attention's roofline cost against the self-attention one, its plain
reference against the program on seeded weights, and a whole tiny CPU cell
of it."""
import importlib.util
import json
import sys
import time

import jax
import numpy as np
import pytest

from bench import run
from bench.lib import cost, reference, spec
from bench_fixtures import CLOSED, REPO

PVT = spec.load_family(REPO, {"family": "pvt"})
B2 = json.loads((REPO / "bench/configs/pvtv2-b2-shiftadd.json").read_text())

# PVTv2 at a size the CPU runs in seconds: 64 px, grids 16/8/4/2, spatial
# reduction in three stages (to 2x2), head dims 16/16/8/8.
TINY_PVT = dict(
    image_size=64, in_channels=3, n_classes=10, widths=[16, 32, 40, 64],
    depths=[1, 1, 2, 1], heads=[1, 2, 5, 8], mlp_ratios=[8, 8, 4, 4],
    sr_ratios=[8, 4, 2, 1], patch_kernels=[7, 3, 3, 3],
    patch_strides=[4, 2, 2, 2],
    activation_dtype="float32", moe_experts=["mult", "shift"],
    moe_capacity_factor=1.25,
    moe_capacity_per_image=[[158, 163], [37, 44], [8, 12], [2, 4]])
ARMS = {"shiftadd": 2, "dense": 0}


def tiny(arm):
    return dict(TINY_PVT, name=f"tiny-pvt-{arm}", family="pvt", policy=arm,
                convert_stage=ARMS[arm])


def _b2(arm):
    return dict(B2, policy=arm, convert_stage=ARMS[arm])


def test_dense_ops_per_image_is_the_papers_count():
    # PVTv2-B2: 4.0 GMAC per 224-px image (arXiv:2106.13797, Table 2);
    # 4.03 G by the equations, two operations a MAC.
    assert PVT.cost.ops_per_image(_b2("dense")) == pytest.approx(
        2 * 4.03e9, rel=0.01)


def test_ops_per_image_by_hand():
    # Stage 4 of B2 alone (49 tokens, 512 wide, 8 heads, hidden 2048, no
    # reduction) is one term of the sum: checked against a count by hand.
    # A 31x31 embedding of stride 32 (padding 15) gives its 7x7 grid.
    cfg = dict(_b2("shiftadd"), widths=[512], depths=[3], heads=[8],
               mlp_ratios=[4], sr_ratios=[1], patch_kernels=[31],
               patch_strides=[32], moe_capacity_per_image=[[22, 40]])
    block = (4 * 49 * 512 * 512 + 8 * (49 + 49) * 64 * 64 + 3 * 49 * 512
             + 2 * 49 * 512 + 2 * 49 * 512 * 2048 + 9 * 49 * 2048)
    macs = 49 * 31 * 31 * 3 * 512 + 3 * block + 512 * 1000
    assert PVT.cost.ops_per_image(cfg) == pytest.approx(2 * macs)
    assert PVT.cost.ops_per_image(_b2("shiftadd")) == pytest.approx(
        7.988e9, rel=1e-3)


def test_calls_of_one_shiftadd_forward():
    calls = PVT.cost.kernel_calls(_b2("shiftadd"), 32)
    shift = calls["shift_matmul"]
    assert len(shift) == 16 * 6 and calls["bidir_attn"] == []
    # The first block: q, k, v, o, then the shift expert's fc1 and fc2 on
    # its 1969 capacity rows an image.
    assert shift[:6] == [(32 * 3136, 64, 64), (32 * 49, 64, 64),
                         (32 * 49, 64, 64), (32 * 3136, 64, 64),
                         (32 * 1969, 64, 512), (32 * 1969, 512, 64)]
    kv = calls["bidir_attn_kv"]
    assert kv == ([(32, 3136, 49, 64, 64)] * 3 + [(64, 784, 49, 64, 64)] * 4
                  + [(160, 196, 49, 64, 64)] * 6 + [(256, 49, 49, 64, 64)] * 3)
    assert PVT.cost.kernel_calls(_b2("dense"), 32) == {
        "shift_matmul": [], "bidir_attn": [], "bidir_attn_kv": []}


def _reader(name):
    path = REPO / "bench" / "metrics" / f"{name}.py"
    module_spec = importlib.util.spec_from_file_location(f"t_{name}", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("g, n, dk, dv", [(96, 196, 64, 64), (32, 49, 64, 64),
                                          (8, 3136, 32, 48)])
def test_reduced_attention_cost_is_the_self_attention_cost_at_equal_lengths(
        g, n, dk, dv):
    reader = _reader("reduced_attn_roofline")
    assert reader.reduced_attn_cost(g, n, n, dk, dv) == cost.bidir_attn_cost(
        g, n, dk, dv)


def test_reduced_attention_cost_by_hand():
    ops, nbytes = _reader("reduced_attn_roofline").reduced_attn_cost(
        32, 3136, 49, 64, 64)
    assert ops == 32 * (2 * 49 * 64 * 64 + 2 * 3136 * 64 * 64
                        + (49 + 3136) * 128)
    assert nbytes == 4 * 32 * (3136 * 64 * 2 + 49 * 64 * 2)


# On the CPU both precisions compute in float32, and the program and the
# reference differ only in the order of their sums (a convolution as a
# matmul of its windows in the reference, conv_general_dilated in the
# program; the MoE's plain selects against its dispatch): they agree to about
# 1e-6 of the largest logit. The bound leaves room for that over the five
# blocks; a wrong equation (a flipped kernel, a missed mask, the tanh GELU
# for the erf one) reads 1e-3 or more.
TOL = {"highest": 1e-4, "default": 1e-4}


@pytest.mark.parametrize("precision", ["highest", "default"])
@pytest.mark.parametrize("arm", ["shiftadd", "dense"])
def test_reference_matches_the_program_on_the_cpu(arm, precision):
    from repro.serve.vision import BucketedViTEngine

    cfg = tiny(arm)
    weights = PVT.weights.make(cfg, 3157000011)
    model, params = PVT.program.build(cfg, weights)
    engine = BucketedViTEngine(model, params, buckets=(4,), impl="xla")
    images = np.random.default_rng(5).integers(0, 256, (4, 64, 64, 3),
                                               dtype=np.uint8)
    with jax.default_matmul_precision(precision):
        served = np.asarray(engine.infer(images))
    want = reference.logits(PVT, weights, images, cfg, precision=precision,
                            block=4)
    err = np.abs(served - want).max() / np.abs(want).max()
    assert err < TOL[precision], err


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("arm", ["shiftadd", "dense"])
def test_reference_has_no_gather(arm):
    # Strided indexing and per-token lookups lower to gathers, slow on a
    # TPU: with them the reference took 49-53 s over the 256 images of a
    # PVTv2-B2 check on a v5e.
    cfg = tiny(arm)
    weights = PVT.weights.make(cfg, 1)
    images = np.zeros((2, 64, 64, 3), np.uint8)
    jaxpr = jax.make_jaxpr(
        lambda w, x: PVT.reference.forward(w, x, cfg))(weights, images)
    prims = set(_primitives(jaxpr.jaxpr))
    assert "gather" not in prims and "dot_general" in prims


def test_configuration_capacities_are_what_the_program_plans():
    from repro.nn.pvt import PVTv2, PVTv2Config
    from repro.core.policy import SHIFTADD

    model = PVTv2(PVTv2Config(policy=SHIFTADD))
    assert model.token_counts == (3136, 784, 196, 49)
    planned = [list(st["blocks"][0].feed.capacity_plan(n)[0])
               for st, n in zip(model.stages, model.token_counts)]
    assert planned == B2["moe_capacity_per_image"]


def test_build_refuses_capacities_the_program_does_not_plan():
    cfg = dict(tiny("shiftadd"),
               moe_capacity_per_image=[[158, 163], [37, 44], [8, 12], [3, 3]])
    with pytest.raises(spec.SpecError):
        PVT.program.build(cfg, PVT.weights.make(cfg, 1))


def test_a_program_without_pvt_is_refused_when_the_family_loads(monkeypatch):
    # A None entry makes the import fail as if the module were not there.
    monkeypatch.setitem(sys.modules, "repro.nn.pvt", None)
    with pytest.raises(spec.SpecError, match="no PVTv2"):
        spec.load_family(REPO, {"family": "pvt"})


@pytest.fixture
def pvt_root(tiny_root):
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    for arm in ARMS:
        cfg = dict(tiny(arm), limits={"logit_err_highest": 1e-4,
                                      "logit_err_default": 1e-4})
        file = f"bench/configs/{cfg['name']}.json"
        (tiny_root / file).write_text(json.dumps(cfg))
        bench["configs"].append({"name": cfg["name"], "file": file})
        bench["workloads"].append({"name": f"{cfg['name']}.closed",
                                   "config": cfg["name"], "traffic": "closed",
                                   "chips": 1})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    (tiny_root / "bench" / "traffic" / "closed.json").write_text(
        json.dumps(dict(CLOSED, payload_pool=16)))
    return tiny_root


@pytest.mark.parametrize("arm", list(ARMS))
def test_a_whole_tiny_cell_passes_the_check(pvt_root, arm):
    res = run.run_cell(pvt_root, f"tiny-pvt-{arm}.closed", 4102000011, 1.0,
                       False, impl="xla", t_process=time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["images_per_s"]["value"] > 0
