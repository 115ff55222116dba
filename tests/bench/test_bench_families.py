"""Model families: a configuration names the directory of its model under
`bench/families/`, which the harness finds by name; a family added as files
runs a whole cell; the plain reference and the counts stay apart from the
program; the vit family makes the weights it made before it was a family."""
import ast
import functools
import json
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.lib import spec
from bench_fixtures import REPO, TINY, TINY_LIMITS

FAMILIES = sorted(p.name for p in (REPO / "bench" / "families").iterdir()
                  if p.is_dir() and p.name != "__pycache__")


def test_a_family_added_as_files_runs_a_whole_cell(tiny_root):
    # What a change that adds an architecture adds: the family's directory,
    # a configuration that names it, and its entries in BENCHMARK.json.
    families = tiny_root / "bench" / "families"
    shutil.copytree(families / "vit", families / "vit-copy",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = dict(TINY, name="tiny-copy", family="vit-copy", policy="shiftadd",
               convert_stage=2, limits=TINY_LIMITS)
    file = "bench/configs/tiny-copy.json"
    (tiny_root / file).write_text(json.dumps(cfg))
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-copy", "file": file})
    bench["workloads"].append({"name": "tiny-copy.closed",
                               "config": "tiny-copy", "traffic": "closed",
                               "chips": 1})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))

    family = spec.load_family(tiny_root, cfg)
    assert family.name == "vit-copy"
    for module in (family.weights, family.program, family.reference,
                   family.cost):
        assert Path(module.__file__).parent == families / "vit-copy"
    res = run.run_cell(tiny_root, "tiny-copy.closed", 11, 1.0, False,
                       impl="xla", t_process=time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def _no_such_family(root):
    return {"name": "c", "family": "no-such-family"}


def _no_family_key(root):
    return {"name": "c"}


def _a_file_left_out(root):
    (root / "bench" / "families" / "vit" / "cost.py").unlink()
    return {"name": "c", "family": "vit"}


def _a_function_left_out(root):
    path = root / "bench" / "families" / "vit" / "cost.py"
    path.write_text(path.read_text().replace("def kernel_calls(",
                                             "def other_calls("))
    return {"name": "c", "family": "vit"}


@pytest.mark.parametrize("case", [_no_such_family, _no_family_key,
                                  _a_file_left_out, _a_function_left_out])
def test_load_family_refuses_what_the_files_do_not_define(tiny_root, case):
    cfg = case(tiny_root)
    with pytest.raises(spec.SpecError):
        spec.load_family(tiny_root, cfg)


@pytest.mark.parametrize("file", ["reference.py", "weights.py", "cost.py"])
@pytest.mark.parametrize("family", FAMILIES)
def test_reference_weights_and_counts_import_nothing_of_the_program(family,
                                                                   file):
    tree = ast.parse((REPO / "bench" / "families" / family / file).read_text())
    imported = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            imported.append(node.module or "")
    assert not [m for m in imported
                if m == "repro" or m.startswith("repro.")], imported


# The weights as the harness made them before the model became a family
# (`bench/lib/weights.py`), frozen here: the same seed has to give the same
# weights, bit for bit.
def _frozen_key_from_seed(seed):
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0]) >> 1
    return jax.random.PRNGKey(word)


def _frozen_linear(key, d_in, d_out):
    kw, kb = jax.random.split(key)
    w = jax.random.truncated_normal(kw, -2.0, 2.0, (d_in, d_out)) * d_in ** -0.5
    return {"kernel": w, "bias": 0.02 * jax.random.normal(kb, (d_out,))}


def _frozen_norm(key, d):
    ks, kb = jax.random.split(key)
    return {"scale": 1.0 + 0.1 * jax.random.normal(ks, (d,)),
            "bias": 0.05 * jax.random.normal(kb, (d,))}


def _frozen_make(key, *, patch_dim, d, d_ff, n_layers, n_classes, n_experts):
    keys = jax.random.split(key, n_layers + 4)
    pe = _frozen_linear(keys[0], patch_dim, d)
    pe = {"kernel": pe["kernel"] / 127.5,
          "bias": pe["bias"] - jnp.sum(pe["kernel"], axis=0)}
    blocks, routers, dwconvs = [], [], []
    for i in range(n_layers):
        k = jax.random.split(keys[1 + i], 10)
        blocks.append({
            "mixer": {name: _frozen_linear(k[j], d, d)
                      for j, name in enumerate(("q", "k", "v", "o"))},
            "feed": {"up": _frozen_linear(k[4], d, d_ff),
                     "down": _frozen_linear(k[5], d_ff, d)},
            "norm1": _frozen_norm(k[6], d),
            "norm2": _frozen_norm(k[7], d),
        })
        routers.append(jax.random.normal(k[8], (d, n_experts)) * d ** -0.5)
        kc, kb = jax.random.split(k[9])
        dwconvs.append({
            "kernel": jax.random.normal(kc, (3, d)) * 3 ** -0.5,
            "bias": 0.02 * jax.random.normal(kb, (d,))})
    dense = {"patch_embed": pe, "blocks": blocks,
             "final_norm": _frozen_norm(keys[-3], d),
             "head": _frozen_linear(keys[-2], d, n_classes)}
    return {"dense": dense, "router": routers, "dwconv": dwconvs}


@functools.lru_cache(maxsize=None)
def _frozen_maker(patch_dim, d, d_ff, n_layers, n_classes, n_experts):
    return jax.jit(functools.partial(
        _frozen_make, patch_dim=patch_dim, d=d, d_ff=d_ff, n_layers=n_layers,
        n_classes=n_classes, n_experts=n_experts))


# Loaded once, so that the family's jitted maker compiles once for the
# file's cases (about 20 s on the CPU at DeiT-Ti's widths).
VIT = spec.load_family(REPO, {"family": "vit"})


@pytest.mark.parametrize("seed", [0, 3157000011])
@pytest.mark.parametrize("arm", ["shiftadd", "dense"])
def test_vit_weights_are_the_ones_made_before_families(arm, seed):
    cfg = json.loads((REPO / f"bench/configs/deit-tiny-{arm}.json").read_text())
    assert cfg["family"] == "vit"
    frozen = _frozen_maker(cfg["patch_size"] ** 2 * cfg["in_channels"],
                           cfg["d_model"], cfg["d_ff"], cfg["n_layers"],
                           cfg["n_classes"],
                           len(cfg.get("moe_experts", ("mult", "shift"))))
    want = frozen(_frozen_key_from_seed(seed))
    got = VIT.weights.make(cfg, seed)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a), np.asarray(b))
