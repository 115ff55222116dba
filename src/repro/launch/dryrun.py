import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The lines above MUST run before any other import — jax locks the device
count at first init. Do not set that flag globally (smoke tests and benches
must see 1 device). The dry-run is a CPU tool on 512 fake host devices:
JAX_PLATFORMS=cpu keeps it, and every child `--all` spawns (they inherit
the environment), off an attached accelerator, which belongs to one
process at a time.

Per cell:
  train_4k    → jax.jit(train_step)   (state donated, microbatched)
  prefill_32k → jax.jit(prefill_step)
  decode_32k / long_500k → jax.jit(serve_step) (cache donated)

Artifacts (one JSON per cell) carry: memory_analysis, XLA cost_analysis,
and the trip-count-corrected HLO costs (launch.hlo_analysis) that feed
§Roofline. All numbers are per-device (post-SPMD HLO).

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both          # orchestrates
                                                           # subprocesses
"""
import argparse
import json
import math
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import shapes as shp
from repro.configs.base import TrainConfig
from repro.configs.registry import get_config, list_archs
from repro.distributed import sharding as shard_lib
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.nn.model import LanguageModel
from repro.serve.decode import make_prefill_step, make_serve_step
from repro.train.step import init_train_state, make_train_step
from repro.utils.logging import get_logger

log = get_logger("repro.dryrun")

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")

# Cache-leaf logical axes by param name (see DESIGN.md §3).
CACHE_RULES = {
    "k": ("batch", "kv_heads", None, None),
    "v": ("batch", "kv_heads", None, None),
    "k_scale": ("batch", "kv_heads", None),
    "v_scale": ("batch", "kv_heads", None),
    "kv": ("batch", "heads", None, None),
    "ksum": ("batch", "heads", None),
    "vsum": ("batch", "heads", None),
    "S": ("batch", "heads", None, None),
    "c_kv": ("batch", None, None),
    "k_rope": ("batch", None, None),
    "conv": ("batch", None, None),
    "x_prev": ("batch", None),
    "h": ("batch", None),
    "slot_pos": ("batch", None),
    "pos": ("batch",),
    "count": ("batch",),
}


# Cache leaves that may fall back to sharding their LAST dim over `model`
# when the head dim is indivisible (e.g. kv_heads=8 on model=16) — otherwise
# a 32k dense KV cache replicates 16× and blows the HBM budget.
_KV_LIKE = {"k", "v", "kv", "S", "c_kv", "k_rope", "ksum"}


def cache_shardings(cache_shapes, mesh):
    model_size = mesh.shape.get("model", 1)

    def one(path, leaf):
        name = None
        for p in reversed(path):
            if hasattr(p, "key"):
                name = p.key
                break
        axes = CACHE_RULES.get(name, ())
        if leaf.ndim == len(axes) + 1:      # scan-stacked (cycles, ...)
            axes = (None,) + tuple(axes)
        elif leaf.ndim != len(axes):
            axes = (None,) * leaf.ndim
        pspec = shard_lib.logical_to_pspec(axes, mesh, leaf.shape)
        if (name in _KV_LIKE and "model" in mesh.axis_names
                and "model" not in jax.tree_util.tree_leaves(tuple(pspec))
                and leaf.ndim >= 2 and leaf.shape[-1] % model_size == 0):
            axes = tuple(axes[:-1]) + ("mlp",)   # mlp → model
            pspec = shard_lib.logical_to_pspec(axes, mesh, leaf.shape)
        return NamedSharding(mesh, pspec)

    return jax.tree_util.tree_map_with_path(one, cache_shapes)


def state_shardings(state_shapes, params_shardings, mesh, opt_shard=None):
    rep = NamedSharding(mesh, P())
    opt_shard = opt_shard if opt_shard is not None else params_shardings

    def like_params(tree_shapes):
        # m/v mirror params (ZeRO: may be sharded more finely than params);
        # frozen (int) leaves became f32 scalars → replicate
        flat_p, treedef = jax.tree_util.tree_flatten(state_shapes["params"])
        flat_sh = treedef.flatten_up_to(opt_shard)
        flat_t = treedef.flatten_up_to(tree_shapes)
        out = [sh if t.shape == p.shape else rep
               for p, sh, t in zip(flat_p, flat_sh, flat_t)]
        return treedef.unflatten(out)

    out = {"params": params_shardings, "step": rep}
    opt = state_shapes["opt"]
    out["opt"] = type(opt)(count=rep, m=like_params(opt.m), v=like_params(opt.v))
    if "ef" in state_shapes:
        out["ef"] = like_params(state_shapes["ef"])
    return out


def batch_shardings(batch_specs, mesh):
    def one(leaf):
        axes = ("batch",) + (None,) * (leaf.ndim - 1)
        return NamedSharding(mesh, shard_lib.logical_to_pspec(axes, mesh, leaf.shape))

    return jax.tree_util.tree_map(one, batch_specs)


def lower_cell(arch, shape_name, mesh_kind, policy=None, n_micro=None,
               remat=None, cast_params="none", shard_mode="baseline",
               constrain_grad_acc=False, moe_cap=None):
    cfg = get_config(arch, policy=policy)
    if remat is not None:
        cfg = cfg.replace(remat=remat)
    if moe_cap is not None:
        cfg = cfg.replace(moe_primitives_capacity=moe_cap)
    if os.environ.get("REPRO_RWKV_CHUNKED"):
        cfg = cfg.replace(rwkv_chunked=True)
    if os.environ.get("REPRO_KV_INT8"):
        cfg = cfg.replace(kv_cache_dtype="int8")
    plan = shp.plan_cell(cfg, shape_name)
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
              "policy": policy or "dense"}
    if plan.skip:
        result.update(skipped=True, reason=plan.reason)
        return result
    if plan.policy_override is not None:
        cfg = cfg.with_policy(plan.policy_override)
        result["policy"] = "shiftadd(auto: long-context requires sub-quadratic)"

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    spec = shp.SHAPES[shape_name]
    model = LanguageModel(cfg)
    key = jax.random.PRNGKey(0)

    t0 = time.time()
    shard_lib.set_active_mesh(mesh)
    with mesh:
        params_shapes = jax.eval_shape(model.init, key)
        n_params = sum(math.prod(l.shape) if l.shape else 1
                       for l in jax.tree_util.tree_leaves(params_shapes))
        pspec_tree = model.spec(params_shapes)
        opt_spec_tree = pspec_tree
        if shard_mode == "out_fsdp":
            pspec_tree = shard_lib.spec_to_out_fsdp(pspec_tree)
            opt_spec_tree = pspec_tree
        elif shard_mode == "tp_zero1":
            pspec_tree = shard_lib.spec_to_tp_zero1(pspec_tree)
        pshard = shard_lib.shardings_from_spec(pspec_tree, params_shapes, mesh)
        opt_shard = (pshard if opt_spec_tree is pspec_tree else
                     shard_lib.shardings_from_spec(opt_spec_tree, params_shapes,
                                                   mesh))

        if spec.kind == "train":
            # Microbatch count: keep per-microbatch batch divisible by the DP
            # shard count (pod×data), else GSPMD pads every activation 2×.
            dp = mesh.devices.size // mesh.shape.get("model", 1)
            default_micro = max(1, min(16, spec.global_batch // dp))
            tcfg = TrainConfig(global_batch=spec.global_batch, seq_len=spec.seq_len,
                               microbatch=n_micro or default_micro,
                               cast_params=cast_params,
                               constrain_grad_acc=constrain_grad_acc)
            state_shapes = jax.eval_shape(
                lambda k: init_train_state(model, tcfg, k), key)
            st_shard = state_shardings(state_shapes, pshard, mesh,
                                       opt_shard=opt_shard)
            batch = shp.input_specs(cfg, shape_name)
            b_shard = batch_shardings(batch, mesh)
            step = make_train_step(model, tcfg)
            lowered = jax.jit(step, in_shardings=(st_shard, b_shard),
                              out_shardings=(st_shard, None),
                              donate_argnums=(0,)).lower(state_shapes, batch)
        elif spec.kind == "prefill":
            batch = shp.input_specs(cfg, shape_name)
            b_shard = batch_shardings(batch, mesh)
            step = make_prefill_step(model)
            lowered = jax.jit(step, in_shardings=(pshard, b_shard)
                              ).lower(params_shapes, batch)
        else:  # decode
            inputs_t = shp.input_specs(cfg, shape_name)["inputs_t"]
            in_shard = batch_shardings({"t": inputs_t}, mesh)["t"]
            cache_shapes = jax.eval_shape(
                lambda: model.init_cache(spec.global_batch, max_len=spec.seq_len))
            c_shard = cache_shardings(cache_shapes, mesh)
            step = make_serve_step(model)
            lowered = jax.jit(step, in_shardings=(pshard, in_shard, c_shard),
                              out_shardings=(None, c_shard),
                              donate_argnums=(2,)
                              ).lower(params_shapes, inputs_t, cache_shapes)
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis() or {}
    hlo_cost = hlo_analysis.analyze(compiled.as_text())

    tokens = spec.global_batch * (spec.seq_len if spec.kind != "decode" else 1)
    # MODEL_FLOPS conventions per kind (6ND train, 2ND forward), N = active
    # params (MoE) excluding nothing — ratio analysis reported alongside.
    active_ratio = cfg.active_param_count() / max(cfg.param_count(), 1)
    n_active = n_params * active_ratio
    mf = {"train": 6.0, "prefill": 2.0, "decode": 2.0}[spec.kind]
    model_flops = mf * n_active * tokens

    result.update(
        skipped=False,
        kind=spec.kind,
        seq_len=spec.seq_len,
        global_batch=spec.global_batch,
        n_devices=mesh.devices.size,
        n_params=n_params,
        n_params_active=n_active,
        model_flops_global=model_flops,
        lower_seconds=t_lower,
        compile_seconds=t_compile,
        memory={
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        xla_cost={k: v for k, v in xla_cost.items()
                  if k in ("flops", "bytes accessed")},
        hlo_flops_per_device=hlo_cost.flops,
        hlo_bytes_per_device=hlo_cost.bytes,
        collective_bytes_per_device=hlo_cost.collective_bytes,
        collective_breakdown=hlo_cost.collective_breakdown,
    )
    return result


def artifact_path(arch, shape_name, mesh_kind, policy, out_dir=None):
    d = out_dir or ARTIFACT_DIR
    os.makedirs(d, exist_ok=True)
    pol = policy or "dense"
    return os.path.join(d, f"{arch}__{shape_name}__{mesh_kind}__{pol}.json")


def run_one(args):
    res = lower_cell(args.arch, args.shape, args.mesh, args.policy,
                     n_micro=args.microbatch, remat=args.remat,
                     cast_params=args.cast_params, shard_mode=args.shard_mode,
                     constrain_grad_acc=args.grad_acc, moe_cap=args.moe_cap)
    res["variant"] = args.variant
    path = artifact_path(args.arch, args.shape, args.mesh, args.policy,
                         args.out)
    if args.variant:
        path = path.replace(".json", f"__{args.variant}.json")
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    log.info("wrote %s", path)
    status = "SKIP" if res.get("skipped") else "OK"
    extra = res.get("reason", "") if res.get("skipped") else (
        f"compile={res['compile_seconds']:.1f}s "
        f"temp={res['memory']['temp_bytes']/2**30:.2f}GiB "
        f"flops/dev={res['hlo_flops_per_device']:.3e}")
    print(f"[{status}] {args.arch} {args.shape} {args.mesh} "
          f"{args.policy or 'dense'}: {extra}")
    return 0


def run_all(args):
    """Orchestrate every cell in subprocesses (isolation + parallelism)."""
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = [(a, s, m) for a in list_archs() for s in shp.SHAPES for m in meshes]
    procs = []
    failures = []
    max_par = args.jobs

    def launch(a, s, m):
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", a,
               "--shape", s, "--mesh", m]
        if args.policy:
            cmd += ["--policy", args.policy]
        if args.out:
            cmd += ["--out", args.out]
        env = dict(os.environ)
        env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
        return subprocess.Popen(cmd, env=env)

    pending = list(cells)
    running = []
    while pending or running:
        while pending and len(running) < max_par:
            a, s, m = pending.pop(0)
            running.append(((a, s, m), launch(a, s, m)))
        done = [(c, p) for c, p in running if p.poll() is not None]
        running = [(c, p) for c, p in running if p.poll() is None]
        for cell, p in done:
            if p.returncode != 0:
                failures.append(cell)
                print(f"[FAIL] {cell}")
        time.sleep(0.5)
    print(f"\n{len(cells) - len(failures)}/{len(cells)} cells OK")
    if failures:
        print("failures:", failures)
        return 1
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=list(shp.SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--policy", choices=["dense", "shiftadd", "shiftadd_deploy",
                                         "stage1", "all_shift"], default=None)
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--remat", choices=["none", "full", "dots_saveable"],
                    default=None)
    ap.add_argument("--cast-params", dest="cast_params",
                    choices=["none", "compute_dtype"], default="none")
    ap.add_argument("--shard-mode", dest="shard_mode",
                    choices=["baseline", "out_fsdp", "tp_zero1"],
                    default="baseline")
    ap.add_argument("--grad-acc-constraint", dest="grad_acc",
                    action="store_true")
    ap.add_argument("--moe-cap", dest="moe_cap", type=float, default=None)
    ap.add_argument("--variant", default=None,
                    help="suffix for §Perf hillclimb artifacts")
    ap.add_argument("--out", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=4)
    args = ap.parse_args()
    if args.all:
        sys.exit(run_all(args))
    assert args.arch and args.shape and args.mesh in ("single", "multi")
    sys.exit(run_one(args))


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
