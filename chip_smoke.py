"""Smoke run of the frozen ShiftAddViT serving path on a TPU.

    python chip_smoke.py               # one chip: ViT phase + LM phase
    python chip_smoke.py --four-chips  # four chips: data-parallel arm only

ViT phase, at DeiT-Tiny width (224 px, patch 16, 12 layers, d_model 192,
3 heads, d_ff 768, 1000 classes; seeded random weights pushed through
`convert_from`): for each of the dense, stage1 and shiftadd arms, a frozen
`BucketedViTEngine(impl="pallas")` and its `impl="xla"` twin are warmed on
buckets (1, 8, 32) and serve mixed-size requests (padding and the oversize
split included). The run fails on a retrace after warmup, non-finite or
misshapen logits, a compiled pallas program whose Mosaic kernels
(`tpu_custom_call`) are not exactly `KERNELS_PER_LAYER` per layer, or pallas
logits outside `REL_BOUND` of the xla twin.

LM phase: `BucketedLMEngine` at the `lm_traffic_sweep` geometry serves
prefill + decode for the stage1 and shiftadd arms; greedy tokens must equal
`serve.decode.generate`, the oracle the CPU tests hold the engine to.

--four-chips: the data-parallel replica arm (`make_replicas(arm="sharded")`,
the `batch -> data` mesh rule) serving DeiT-Tiny shiftadd on four devices,
compared per image with a one-chip engine on device 0 in the same process.

The script runs in one process, which holds the chip(s). It exits non-zero,
printing no result line, unless JAX's first device is a TPU. Its last line
is one JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
# libtpu writes its logs here instead of the shared /tmp/tpu_logs.
TPU_LOGS = ROOT / ".tpu_logs"

SEED = 0
DEIT_TINY = dict(image_size=224, patch_size=16, in_channels=3,
                 n_classes=1000, n_layers=12, d_model=192, n_heads=3,
                 d_ff=768)
BUCKETS = (1, 8, 32)
# Sizes cover: bucket exact (1, 8, 32), padding (3 -> 8, 17 -> 32) and the
# oversize split (40 -> 32 + 8).
REQUEST_SIZES = (1, 3, 8, 17, 32, 40)
ARMS = ("dense", "stage1", "shiftadd")
# Mosaic kernels per layer in an arm's compiled program: stage1 runs the
# fused bidirectional attention; shiftadd adds the four shift projections
# (q, k, v, o) and the two shift-expert linears. A kernel that fell back to
# XLA lowers the count.
KERNELS_PER_LAYER = {"dense": 0, "stage1": 1, "shiftadd": 7}
TIMING_ROUNDS = 5

# Pallas vs XLA-twin bound, relative to the largest |logit| of the twin.
# shift_matmul rounds activations to bf16 before the MXU and the twin's f32
# dots run at the TPU's default one-pass bf16 precision, so the two differ
# by rounding alone. On a v5e at this config and seed that came to 1.4e-3
# (shiftadd) and 9.1e-4 (stage1) of the largest logit; 2^-8 = 3.9e-3 sits
# 2.8x above the larger. A planted kernel fault, shift exponent -9 decoded
# as -8 (2% of the shift weights), moves the logits by 1.3e-2 of the
# largest one on CPU and fails it.
REL_BOUND = 2.0 ** -8

LM_GEOMETRY = dict(n_layers=2, d_model=64, vocab=256, n_slots=4,
                   prompt_buckets=(4, 8, 16), chunk=4)
# (prompt length, new tokens): bucket-exact, padded and clipped-free prompts,
# and new-token counts that end inside and on a chunk boundary.
LM_REQUESTS = ((3, 9), (8, 6), (12, 8), (16, 5))


def check(cond, msg):
    """A failed check ends the run with a non-zero exit (asserts vanish
    under -O)."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def log(msg):
    print(msg, flush=True)


def device_info():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def vit_setup():
    import jax

    from repro.core.policy import DENSE
    from repro.nn.vit import ShiftAddViT, ViTConfig

    cfg = ViTConfig(**DEIT_TINY)
    dense_model = ShiftAddViT(dataclasses.replace(cfg, policy=DENSE))
    dense_params = dense_model.init(jax.random.PRNGKey(SEED))
    return cfg, dense_model, dense_params


def make_requests(cfg, sizes=REQUEST_SIZES):
    import jax

    shape = (cfg.image_size, cfg.image_size, cfg.in_channels)
    return [jax.random.normal(jax.random.PRNGKey(SEED + 1 + i), (n,) + shape)
            for i, n in enumerate(sizes)]


def compare(name, got, want):
    """Max |Δ| and top-1 agreement of two logit arrays against REL_BOUND.
    Top-1 must agree wherever the reference's top-2 margin exceeds twice
    the bound: a flip inside the error bound is not a disagreement."""
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    check(got.shape == want.shape, f"{name}: shape {got.shape} vs {want.shape}")
    scale = float(np.abs(want).max())
    bound = REL_BOUND * scale
    delta = float(np.abs(got - want).max())
    top2 = np.sort(want, axis=-1)[:, -2:]
    decided = (top2[:, 1] - top2[:, 0]) > 2 * bound
    agree = got.argmax(-1) == want.argmax(-1)
    log(f"  {name}: max|d|={delta:.3e} bound={bound:.3e} "
        f"(rel {REL_BOUND:.3e} x {scale:.3f}) top1 {int(agree.sum())}/"
        f"{agree.size} agree, {int(decided.sum())} decided")
    check(delta <= bound, f"{name}: max|d| {delta} > bound {bound}")
    check(bool(agree[decided].all()),
          f"{name}: top-1 differs on a row decided beyond the bound")
    return delta


def warmed_engine(model, params, impl, **kw):
    from repro.serve.vision import BucketedViTEngine

    t0 = time.perf_counter()
    eng = BucketedViTEngine(model, params, buckets=BUCKETS, freeze=True,
                            impl=impl, **kw).warmup()
    return eng, time.perf_counter() - t0


def mosaic_calls(engine, cfg):
    """Mosaic kernels in the engine's compiled bucket-1 program."""
    import jax
    import jax.numpy as jnp

    shape = (1, cfg.image_size, cfg.image_size, cfg.in_channels)
    compiled = engine._call.lower(jax.ShapeDtypeStruct(shape, jnp.float32)).compile()
    return compiled.as_text().count("tpu_custom_call")


def vit_phase():
    import jax
    import numpy as np

    from repro.serve.vision import build_policy_model

    cfg, dense_model, dense_params = vit_setup()
    requests = make_requests(cfg)
    log(f"vit: DeiT-Tiny {DEIT_TINY}, buckets {BUCKETS}, "
        f"request sizes {REQUEST_SIZES}")
    for arm in ARMS:
        model, params = build_policy_model(cfg, arm, dense_model, dense_params)
        eng_p, compile_p = warmed_engine(model, params, "pallas")
        eng_x, compile_x = warmed_engine(model, params, "xla")
        traces = (eng_p.trace_count, eng_x.trace_count)
        log(f"vit {arm}: compile_s pallas={compile_p:.2f} xla={compile_x:.2f} "
            f"(warmup of {len(BUCKETS)} buckets)")
        got_p = [np.asarray(eng_p.infer(r)) for r in requests]
        got_x = [np.asarray(eng_x.infer(r)) for r in requests]
        for r, lp in zip(requests, got_p):
            check(lp.shape == (r.shape[0], cfg.n_classes),
                  f"{arm}: logits shape {lp.shape}")
            check(bool(np.isfinite(lp).all()), f"{arm}: non-finite logits")
        compare(f"vit {arm} pallas-vs-xla", np.concatenate(got_p),
                np.concatenate(got_x))

        times = {n: [] for n in REQUEST_SIZES}
        for _ in range(TIMING_ROUNDS):
            for r in requests:
                t0 = time.perf_counter()
                jax.block_until_ready(eng_p.infer(r))
                times[r.shape[0]].append(time.perf_counter() - t0)
        med = {n: 1e3 * sorted(ts)[len(ts) // 2] for n, ts in times.items()}
        log(f"vit {arm}: pallas median ms per request by size "
            + " ".join(f"{n}:{ms:.3f}" for n, ms in med.items()))

        recompiles = (eng_p.trace_count - traces[0],
                      eng_x.trace_count - traces[1])
        log(f"vit {arm}: recompiles after warmup pallas={recompiles[0]} "
            f"xla={recompiles[1]}")
        check(recompiles == (0, 0), f"{arm}: retraced after warmup")

        # 1-vs-N: the first image of the size-8 request, served alone.
        alone = np.asarray(eng_p.infer(requests[2][:1]))
        one_vs_n = float(np.abs(alone[0] - got_p[2][0]).max())
        log(f"vit {arm}: 1-vs-N max|d| (bucket 1 vs row 0 of bucket 8) "
            f"= {one_vs_n:.3e}")

        calls = mosaic_calls(eng_p, cfg)
        want_calls = KERNELS_PER_LAYER[arm] * cfg.n_layers
        log(f"vit {arm}: tpu_custom_call in compiled pallas program = "
            f"{calls} (expected {want_calls})")
        check(calls == want_calls,
              f"{arm}: {calls} Mosaic kernels in the pallas program, "
              f"expected {want_calls}")


def lm_phase():
    import jax
    import numpy as np

    from repro.configs.base import ModelConfig
    from repro.core.policy import SHIFTADD, STAGE1
    from repro.nn.model import LanguageModel
    from repro.serve.decode import generate
    from repro.serve.replicas import make_lm_replicas

    g = LM_GEOMETRY
    log(f"lm: geometry {g}, requests (prompt, new) {LM_REQUESTS}")
    for name, policy in (("stage1", STAGE1), ("shiftadd", SHIFTADD)):
        cfg = ModelConfig(name=f"lm-smoke-{name}", family="dense",
                          policy=policy, n_layers=g["n_layers"],
                          d_model=g["d_model"], n_heads=2, n_kv_heads=2,
                          d_ff=2 * g["d_model"], vocab_size=g["vocab"],
                          dtype="float32", scan_layers=True, remat="none",
                          moe_primitives_capacity=2.0)
        model = LanguageModel(cfg)
        params = model.init(jax.random.PRNGKey(SEED))
        t0 = time.perf_counter()
        eng = make_lm_replicas(model, params, n_replicas=1,
                               n_slots=g["n_slots"],
                               prompt_buckets=g["prompt_buckets"],
                               chunk=g["chunk"]).warmup().engines[0]
        log(f"lm {name}: compile_s={time.perf_counter() - t0:.2f}")
        traces = eng.trace_count
        rng = np.random.default_rng(SEED)
        prompts = [rng.integers(0, g["vocab"], p).astype(np.int32)
                   for p, _ in LM_REQUESTS]
        # All requests co-resident: one prefill per slot, then shared
        # decode chunks until every stream has its tokens.
        streams = {}
        for slot, prompt in enumerate(prompts):
            first, _ = eng.admit(slot, prompt, rid=slot)
            streams[slot] = [first]
        while any(len(streams[s]) < n for s, (_, n) in enumerate(LM_REQUESTS)):
            toks, _ = eng.decode_chunk()
            for s in streams:
                streams[s].extend(int(t) for t in toks[:, s])
        for s in streams:
            eng.evict(s)
        check(eng.trace_count == traces, f"lm {name}: retraced after warmup")
        for s, (prompt, (plen, n_new)) in enumerate(zip(prompts, LM_REQUESTS)):
            want = np.asarray(generate(model, params, prompt[None], n_new))
            got = np.asarray(streams[s][:n_new])
            check(np.array_equal(got, want[0, plen:]),
                  f"lm {name}: request {s} tokens {got} vs oracle "
                  f"{want[0, plen:]}")
        log(f"lm {name}: {len(LM_REQUESTS)} requests, tokens match the "
            f"generate oracle, recompiles after warmup 0")


def four_chip_phase():
    import jax
    import numpy as np

    from repro.serve.replicas import DataParallelReplicas, make_replicas
    from repro.serve.vision import build_policy_model

    n_dev = len(jax.devices())
    check(n_dev >= 4, f"--four-chips needs 4 devices, JAX has {n_dev}")
    cfg, dense_model, dense_params = vit_setup()
    model, params = build_policy_model(cfg, "shiftadd", dense_model,
                                       dense_params)
    t0 = time.perf_counter()
    pool = make_replicas(model, params, n_replicas=4, arm="sharded",
                         buckets=BUCKETS, impl="pallas").warmup()
    check(isinstance(pool, DataParallelReplicas), f"pool is {type(pool)}")
    log(f"four-chips: sharded shiftadd buckets {pool.buckets} on "
        f"{pool.mesh.devices.size} devices, compile_s "
        f"{time.perf_counter() - t0:.2f}")
    one, compile_one = warmed_engine(model, params, "pallas")
    log(f"four-chips: one-chip engine on {jax.devices()[0]}, compile_s "
        f"{compile_one:.2f}")
    traces = pool.trace_count
    requests = make_requests(cfg)
    got, want = [], []
    for r in requests:
        logits, _ = pool.submit(0, r).result()
        check(logits.shape == (r.shape[0], cfg.n_classes),
              f"sharded logits shape {logits.shape}")
        got.append(np.asarray(logits))
        want.append(np.asarray(one.infer(r)))
    check(pool.trace_count == traces, "sharded arm retraced after warmup")
    got, want = np.concatenate(got), np.concatenate(want)
    check(bool(np.isfinite(got).all()), "sharded arm: non-finite logits")
    compare("four-chips sharded-vs-one-chip", got, want)
    log(f"four-chips: per-image bit-identical = "
        f"{bool(np.array_equal(got, want))}")
    pool.close()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel arm on four devices, "
                         "against a one-chip engine")
    args = ap.parse_args(argv)

    from repro.utils.compile_cache import enable_compile_cache

    if "TPU_LOG_DIR" not in os.environ:
        TPU_LOGS.mkdir(exist_ok=True)
        os.environ["TPU_LOG_DIR"] = str(TPU_LOGS)
    cache = enable_compile_cache()
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is platform "
              f"{dev['platform']!r} ({dev['kind']})", file=sys.stderr)
        return 1
    log(f"device: {dev['kind']} x{dev['count']}, compile cache {cache}")
    if args.four_chips:
        four_chip_phase()
    else:
        vit_phase()
        lm_phase()
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
