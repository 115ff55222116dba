"""Batched ShiftAddViT inference engine — the paper's model, served.

Three pieces (DESIGN: measure the paper's headline latency/energy claims
end-to-end, not per-layer):

- **Inference forward**: `ShiftAddViT.infer` — train=False fast path with
  clean-logit argmax MoE routing, no rng, no aux-loss computation, and the
  deterministic latency-aware capacities of `MoEPrimitives.capacities`.
  Two calls on the same batch return identical logits.

- **Shape-bucketed batch assembly** (`BucketedViTEngine`): a stream of
  variable-size requests is padded into a small closed set of batch sizes
  (default {1, 8, 32} — the benchmark/CI set, surfaced as `engine.buckets`),
  so jit compiles exactly one program per bucket
  and steady-state traffic never retraces. `trace_count` exposes the compile
  counter the no-recompilation test asserts on. The image buffer is NOT
  donated: (B, H, W, C) inputs can never alias the (B, n_classes) logits, so
  donation was pure dead weight (`engine.donate_argnums`, audited by
  repro.analysis' JX005 rule, records the intent).

- **Deployment freeze** (`freeze=True`, the default): the engine builds a
  `core.deploy.DeployPlan` at construction — every shift weight decoded or
  packed exactly once, the MoE capacity plan warmed for the per-image token
  count — and the jitted forward closes over the frozen params as
  constants. Frozen and unfrozen logits are bit-identical (the decode is
  exact); the freeze only removes the per-call fake-quant/decode work from
  the compiled program. `freeze=False` is the A/B arm the benchmark and CI
  compare against.

- **Policy sweep** (`policy_sweep`): the same pretrained dense params pushed
  through `convert_from` at stage 0/1/2, measured for batch latency,
  throughput, and analytic per-image energy (`vit_energy_per_image`, built
  from core.energy's Tab.-1 unit energies + data-movement terms). Drives
  benchmarks/bench_vit.py → BENCH_vit.json and repro.launch.serve_vit.

**Batch-invariance contract** (ISSUE 5): MoE feeds plan expert capacity PER
IMAGE ROW (`MoEPrimitives.infer` routes one group per batch row with the
memoized per-image `capacity_plan`), so under EVERY sweep policy — shiftadd
included — an image's logits are bit-identical across batch composition,
row order, bucket padding and replica count. Tokens never compete with
another image's tokens for expert slots; the scheduler may co-batch, split
and shed requests freely with zero logit consequences. The property tier
(tests/test_batch_invariance.py) and the traffic gates
(benchmarks/check_traffic.py replay + 1-vs-N on the shiftadd arm) pin this.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp

from repro.core import energy
from repro.core.policy import DENSE, SHIFTADD, STAGE1
from repro.nn.vit import ShiftAddViT, ViTConfig
from repro.serve import spans
from repro.serve.metrics import latency_summary

# The default bucket set IS the benchmark/CI set: bench_vit.py,
# check_vit_freeze.py and the traffic frontend all read the effective set
# off `engine.buckets` instead of re-declaring it (the old default carried
# an extra 128 bucket no serving path compiled — records and gates drifted).
DEFAULT_BUCKETS = (1, 8, 32)


class BucketedViTEngine:
    """Pads variable-size image batches into jit-cached bucket shapes.

    model/params: an image classifier and its (possibly convert_from'd)
    params — `nn.vit.ShiftAddViT` or `nn.pvt.PVTv2`. The engine needs of the
    model `infer(params, images, impl=, tune=)`, `prepare_inference(params,
    impl=, token_counts=, tune=)`, `token_counts` (the per-image token
    counts its MoE feeds route, warmed at build time) and `cfg.image_size`,
    `cfg.in_channels`, `cfg.n_classes`.
    buckets: allowed batch sizes, ascending. Requests larger than the biggest
    bucket are split into max-bucket chunks, so any request size is served.
    freeze: build a core.deploy DeployPlan at engine construction (decode
    every shift weight once, warm MoE capacity plans for the buckets) and
    close the jitted forward over the frozen params as constants — the
    deployment-freeze serving path. freeze=False serves the live params
    (the A/B arm of the freeze benchmark); logits are bit-identical.
    impl: kernel implementation the plan decodes for (default: process-wide
    `kernels.ops.default_impl()`).
    mesh: optional jax Mesh for the data-parallel serving arm. Batches are
    placed with `distributed.sharding.batch_sharding` (the `batch → data`
    logical rule), and each bucket is rounded UP to a multiple of the
    mesh's batch-axis size so every device holds an equal shard.

    The *effective* bucket set (sorted, deduplicated, mesh-rounded) is
    surfaced as `engine.buckets`; benchmark records and CI gates read it
    from here rather than re-declaring their own set.
    """

    def __init__(self, model, params, buckets=DEFAULT_BUCKETS,
                 freeze=True, impl=None, tune=None, mesh=None):
        from repro.kernels import ops

        assert len(buckets) > 0 and min(buckets) >= 1
        self.model = model
        self.params = params
        self.mesh = mesh
        dp = 1
        if mesh is not None:
            from repro.distributed.sharding import LOGICAL_AXIS_RULES
            for ax in LOGICAL_AXIS_RULES["batch"]:
                dp *= mesh.shape.get(ax, 1)
        self._dp = dp
        self.buckets = tuple(sorted(set(
            dp * ((int(b) + dp - 1) // dp) for b in buckets)))
        self.frozen = bool(freeze)
        # The engine's impl is resolved ONCE here and threaded explicitly
        # through model.infer → blocks → kernels.ops on every call, so the
        # plan's weight format and the kernels the jitted forward runs can
        # never disagree. (The old design instead RAISED on any impl that
        # differed from ops.default_impl() — a memoized process global that
        # every impl=None call site silently inherited.)
        self.impl = impl or ops.default_impl()
        self.tune = tune
        self.trace_count = 0        # incremented only when jit (re)traces
        self.batches_served = 0
        self.images_served = 0
        self.padded_images_served = 0   # bucket slots incl. padding
        # Thread-pool replicas share one engine across workers; unguarded
        # '+=' on the counters would drop updates under concurrent infer().
        self._counter_lock = threading.Lock()

        # Donation intent, surfaced for the serving-contract audit (JX005):
        # the image buffer is NEVER donatable — (B, H, W, C) inputs cannot
        # alias the (B, n_classes) logits, so a donate_argnums on it is dead
        # weight XLA warns about ("donated buffers were not usable") and it
        # forced a defensive copy of full-bucket chunks in infer(). Keep ()
        # unless a future output actually matches an input buffer.
        self.donate_argnums = ()
        jit_kw = {}
        rows = None
        if mesh is not None:
            from repro.distributed import sharding as shd
            # Data-parallel arm: rows over the mesh's batch axes, logits
            # back the same way — the distributed/sharding.py batch → data
            # rule, reused verbatim by the vision serving path.
            jit_kw = dict(in_shardings=shd.batch_sharding(mesh, rank=4),
                          out_shardings=shd.batch_sharding(mesh, rank=2))
            rows = jit_kw["in_shardings"].spec

        def per_shard(f, *replicated):
            """Run f on each device's rows (shard_map). The compiler cannot
            partition a Mosaic kernel by itself, and the forward is row-local
            (see the batch-invariance contract), so every device runs the
            one-device program on its shard, weights replicated. The
            kernels' out_shapes carry no varying-axes (vma) type, so the
            check is off."""
            if rows is None:
                return f
            return jax.shard_map(f, mesh=mesh, in_specs=(*replicated, rows),
                                 out_specs=rows, check_vma=False)
        if freeze:
            # The MoE dispatch routes one group per image row, so the only
            # token counts it ever plans capacity for are the model's
            # per-image ones (the patch count; one per stage of a pyramid)
            # — identical across buckets (a bucket changes how many rows
            # are vmapped over, never a row's capacity split).
            self.plan = model.prepare_inference(
                params, impl=self.impl, token_counts=model.token_counts,
                tune=tune)
            run_params = self.plan.params
            impl_, tune_ = self.impl, self.tune

            # Frozen params are closed over, not passed: they are constants
            # of the serving program, never retraced against. impl/tune ride
            # along as explicit closure constants — never a process global.
            def fwd(images):
                # Runs at trace time, not at execution — the compile counter
                # the no-recompilation gate asserts on.
                self.trace_count += 1  # lint: allow(LT004 trace-time compile counter, guarded by gates)
                return model.infer(run_params, images, impl=impl_,
                                   tune=tune_)

            self._fwd = fwd
            self._call = jax.jit(per_shard(fwd),
                                 donate_argnums=self.donate_argnums, **jit_kw)
            self._lower = self._call.lower
        else:
            self.plan = None

            # The live arm keeps the pre-freeze calling convention: params
            # are a per-call ARGUMENT, so XLA cannot constant-fold the
            # per-forward po2 decode out of the program (which would turn
            # the no-freeze benchmark arm into a de-facto frozen one), and
            # a caller that swaps engine.params serves the new weights.
            impl_, tune_ = self.impl, self.tune

            def fwd(p, images):
                self.trace_count += 1  # lint: allow(LT004 trace-time compile counter, guarded by gates)
                return model.infer(p, images, impl=impl_, tune=tune_)

            if jit_kw:
                from repro.distributed import sharding as shd
                jit_kw["in_shardings"] = (shd.replicated(mesh),
                                          jit_kw["in_shardings"])
            self._fwd = fwd
            fwd_j = jax.jit(per_shard(fwd, jax.sharding.PartitionSpec()),
                            **jit_kw)
            self._call = lambda images: fwd_j(self.params, images)
            self._lower = lambda images: fwd_j.lower(self.params, images)

    def bucket_for(self, n: int) -> int:
        """Smallest bucket that fits n (callers chunk to max bucket first)."""
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def warmup(self):
        """Compile every bucket once so serving never pays a trace. With the
        span recorder on, also note each bucket program's instruction ->
        op_name table (`spans.note_program`, key `jit_fwd/<bucket>`): the
        lowering and the executable come from jit's caches, so this neither
        traces nor compiles again."""
        c = self.model.cfg
        shape = (c.image_size, c.image_size, c.in_channels)
        for b in self.buckets:
            images = jnp.zeros((b,) + shape, jnp.float32)
            jax.block_until_ready(self._call(images))
            if spans.enabled():
                text = self._lower(images).compile().as_text()
                spans.note_program(f"jit_fwd/{b}",
                                   spans.entry_op_names(text))
        return self

    def infer(self, images):
        """images: (n, H, W, C), any n ≥ 1 → logits (n, n_classes).

        Chunks to the max bucket, pads each chunk up to its bucket size and
        slices the padding back off. Input dtype is canonicalized to the
        float32 warmup dtype (jit caches key on dtype — a raw uint8 client
        batch must not retrace). After warmup() this never recompiles.
        """
        with spans.span("engine.put", n_images=len(images)):
            images = jnp.asarray(images, jnp.float32)
        n = images.shape[0]
        if n == 0:
            return jnp.zeros((0, self.model.cfg.n_classes), jnp.float32)
        bmax = self.buckets[-1]
        outs = []
        start = 0
        while start < n:
            take = min(bmax, n - start)
            bucket = self.bucket_for(take)
            chunk = images[start:start + take]
            if take < bucket:
                with spans.span("engine.pad", bucket, take):
                    pad = jnp.zeros((bucket - take,) + chunk.shape[1:],
                                    chunk.dtype)
                    chunk = jnp.concatenate([chunk, pad], axis=0)
            with spans.span("engine.enqueue", bucket, take):
                logits = self._call(chunk)
            with spans.span("engine.slice", bucket, take):
                outs.append(logits[:take])
            with self._counter_lock:
                self.batches_served += 1
                self.padded_images_served += bucket
            start += take
        with self._counter_lock:
            self.images_served += n
        if len(outs) == 1:
            return outs[0]
        with spans.span("engine.slice", n_images=n):
            return jnp.concatenate(outs, axis=0)

    @property
    def padding_waste(self) -> float:
        """Lifetime fraction of served bucket slots that were padding."""
        from repro.serve.metrics import padding_waste as _waste
        return _waste(self.images_served, self.padded_images_served)


# ---------------------------------------------------------------------------
# Interleaved freeze A/B (the CI gate's measurement)
# ---------------------------------------------------------------------------

def freeze_ab(base_cfg: ViTConfig = None, batch=32, iters=20, seed=0,
              policy="shiftadd"):
    """Frozen-vs-live A/B of one policy arm, interleaved in one process.

    Two engines over the SAME converted params — one serving the DeployPlan,
    one serving the live tree — timed in alternating rounds so machine-load
    drift hits both arms equally (two sequential benchmark processes on a
    shared runner can drift 20%+ between runs, swamping the ~10-20% freeze
    effect the CI gate checks). Returns the BENCH_vit_freeze_ab.json record.
    """
    base_cfg = base_cfg or ViTConfig()
    dense_model = ShiftAddViT(dataclasses.replace(base_cfg, policy=DENSE))
    dense_params = dense_model.init(jax.random.PRNGKey(seed))
    model, params = build_policy_model(base_cfg, policy, dense_model,
                                       dense_params)
    imgs = jax.random.normal(
        jax.random.PRNGKey(seed + 1),
        (batch, base_cfg.image_size, base_cfg.image_size, base_cfg.in_channels))
    engines = {
        "frozen": BucketedViTEngine(model, params, buckets=(batch,),
                                    freeze=True).warmup(),
        "live": BucketedViTEngine(model, params, buckets=(batch,),
                                  freeze=False).warmup(),
    }
    samples = {name: [] for name in engines}
    for name, eng in engines.items():
        jax.block_until_ready(eng.infer(imgs))      # post-warmup touch
    for _ in range(iters):
        for name, eng in engines.items():
            t0 = time.perf_counter()
            jax.block_until_ready(eng.infer(imgs))
            samples[name].append(time.perf_counter() - t0)
    med = {name: sorted(ts)[len(ts) // 2] for name, ts in samples.items()}
    return {
        "backend": jax.default_backend(),
        "policy": policy,
        "image_size": base_cfg.image_size,
        "batch": batch,
        "iters": iters,
        "frozen_latency_s": med["frozen"],
        "live_latency_s": med["live"],
        "frozen_vs_live": med["frozen"] / med["live"],
        "recompiles_after_warmup": sum(
            e.trace_count - 1 for e in engines.values()),
    }


# ---------------------------------------------------------------------------
# Analytic per-image energy under a policy (paper Tab. 1 / Tab. 3 view)
# ---------------------------------------------------------------------------

def vit_energy_per_image(cfg: ViTConfig) -> dict:
    """Forward energy of one image under cfg.policy, in pJ.

    Composes core.energy's per-op models (45 nm unit energies + DRAM
    movement) over the actual architecture: patch embed, q/k/v/o projections
    (dense vs shift), attention contractions (quadratic softmax vs the
    linear/binary-linear Q(KᵀV) order), and MLPs (dense, shift, or the MoE —
    whose token split follows the same inverse-latency capacity weights the
    dispatcher uses).
    """
    p = cfg.policy
    n, d, f, h = cfg.n_patches, cfg.d_model, cfg.d_ff, cfg.n_heads
    dh = d // h
    total = energy.matmul_energy(n, cfg.patch_size ** 2 * cfg.in_channels, d,
                                 "fp16")                       # patch embed
    if p.projections == "shift":
        proj = energy.shift_matmul_energy
    else:
        proj = lambda m, k, nn: energy.matmul_energy(m, k, nn, "fp16")
    if p.mlp == "moe_primitives":
        # Same per-image token count and normalization the dispatcher's
        # capacity split uses (MoEPrimitives.latencies_at at the serving
        # group size — one image row), so the modeled Mult/Shift token split
        # matches the one served.
        moe_w = energy.inverse_latency_weights(energy.expert_latencies(
            n, d, f, p.moe_experts))
    for _ in range(cfg.n_layers):
        for _ in range(4):                                     # q, k, v, o
            total += proj(n, d, d)
        for _ in range(h):
            if p.attention == "binary_linear":
                total += energy.add_matmul_energy(dh, n, dh)   # KᵀV (MatAdd)
                total += energy.add_matmul_energy(n, dh, dh)   # Q(KᵀV)
            elif p.attention == "linear":
                total += energy.matmul_energy(dh, n, dh, "fp16")
                total += energy.matmul_energy(n, dh, dh, "fp16")
            else:
                total += energy.matmul_energy(n, dh, n, "fp16")  # QKᵀ
                total += energy.matmul_energy(n, n, dh, "fp16")  # AV
        if p.mlp == "moe_primitives":
            for kind, w in zip(p.moe_experts, moe_w):
                t = max(1, round(n * w))
                op = (energy.shift_matmul_energy if kind == "shift"
                      else lambda m, k, nn: energy.matmul_energy(m, k, nn, "fp16"))
                total += op(t, d, f)
                total += op(t, f, d)
        elif p.mlp == "shift":
            total += energy.shift_matmul_energy(n, d, f)
            total += energy.shift_matmul_energy(n, f, d)
        else:
            total += energy.matmul_energy(n, d, f, "fp16")
            total += energy.matmul_energy(n, f, d, "fp16")
    total += energy.matmul_energy(1, d, cfg.n_classes, "fp16")  # pooled head
    return {"total_pj": total.total_pj, "compute_pj": total.compute_pj,
            "dram_pj": total.dram_pj}


# ---------------------------------------------------------------------------
# Policy sweep: same pretrained dense weights, stage 0 / 1 / 2
# ---------------------------------------------------------------------------

SWEEP_POLICIES = {
    # name → (policy, convert_from stage)
    "dense": (DENSE, 0),
    "stage1": (STAGE1, 1),
    "shiftadd": (SHIFTADD, 2),
}


def build_policy_model(base_cfg: ViTConfig, name: str,
                       dense_model: ShiftAddViT, dense_params):
    """A (model, params) pair for one sweep arm: the base config re-policied
    and the pretrained dense params pushed through the paper's conversion."""
    policy, stage = SWEEP_POLICIES[name]
    cfg = dataclasses.replace(base_cfg, policy=policy)
    model = ShiftAddViT(cfg)
    params = model.convert_from(dense_model, dense_params, stage=stage)
    return model, params


def policy_sweep(base_cfg: ViTConfig = None, batch=32, iters=10,
                 buckets=None, seed=0, policies=tuple(SWEEP_POLICIES),
                 freeze=True, impl=None, tune=None):
    """Measure every policy arm on the same pretrained dense weights.

    Returns the BENCH_vit.json record: per-policy batch latency (median over
    `iters` post-warmup runs), throughput, analytic energy per image, and
    the engine's compile count. freeze selects the
    deployment-freeze arm (DeployPlan closed over by the jitted forward) vs
    the live-params arm; the record carries `frozen` and the
    shiftadd-vs-dense latency ratio so the crossover is tracked across PRs.
    impl/tune thread explicitly to every engine (never via a process
    default); each policy arm also reports PER-BUCKET latency summaries
    (`bucket_latency`) — the per-bucket series check_vit_pallas.py gates
    pallas <= xla on.
    """
    base_cfg = base_cfg or ViTConfig()
    buckets = tuple(buckets) if buckets else DEFAULT_BUCKETS
    if batch not in buckets:
        buckets = tuple(sorted(set(buckets) | {batch}))
    dense_model = ShiftAddViT(dataclasses.replace(base_cfg, policy=DENSE))
    dense_params = dense_model.init(jax.random.PRNGKey(seed))
    imgs = jax.random.normal(
        jax.random.PRNGKey(seed + 1),
        (batch, base_cfg.image_size, base_cfg.image_size, base_cfg.in_channels))

    from repro.kernels import ops
    record = {
        "backend": jax.default_backend(),
        "model": (f"shiftadd_vit({base_cfg.n_layers}L,{base_cfg.d_model}d,"
                  f"{base_cfg.n_patches}p)"),
        "image_size": base_cfg.image_size,
        "batch": batch,
        "iters": iters,
        "frozen": bool(freeze),
        "impl": impl or ops.default_impl(),
        "tuned": tune is not None,
        "tune_meta": dict(getattr(tune, "meta", ()) or ()) or None,
        "policies": {},
    }
    for name in policies:
        model, params = build_policy_model(base_cfg, name, dense_model,
                                           dense_params)
        engine = BucketedViTEngine(model, params, buckets=buckets,
                                   freeze=freeze, impl=impl,
                                   tune=tune).warmup()
        # The effective bucket set comes off the engine — records and the
        # CI gate must never re-declare it (the old drift: DEFAULT_BUCKETS
        # advertised a 128 bucket the benchmark path never compiled).
        record.setdefault("buckets", list(engine.buckets))
        traces_after_warmup = engine.trace_count
        jax.block_until_ready(engine.infer(imgs))   # bucket already compiled
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(engine.infer(imgs))
            times.append(time.perf_counter() - t0)
        # Median, not mean: per-batch wall clock on shared CI machines has
        # heavy right-tail noise and the crossover ratio gates CI.
        latency_s = sorted(times)[len(times) // 2]
        # Per-bucket series: the granularity check_vit_pallas.py gates
        # pallas <= xla at. Buckets above the benchmark batch have no full
        # batch to feed and are skipped (never silently zero-filled).
        bucket_latency = {}
        for bkt in engine.buckets:
            if bkt > batch:
                continue
            sub = imgs[:bkt]
            jax.block_until_ready(engine.infer(sub))    # already compiled
            bts = []
            for _ in range(iters):
                t0 = time.perf_counter()
                jax.block_until_ready(engine.infer(sub))
                bts.append(time.perf_counter() - t0)
            bucket_latency[str(bkt)] = latency_summary(bts)
        e = vit_energy_per_image(model.cfg)
        record["policies"][name] = {
            "latency_s_per_batch": latency_s,
            "images_per_s": batch / latency_s,
            # Same summary schema as BENCH_traffic.json (serve.metrics):
            # here the samples are per-batch sweep latencies.
            "latency": latency_summary(times),
            "bucket_latency": bucket_latency,
            "buckets": list(engine.buckets),
            "padding_waste": engine.padding_waste,
            "energy_pj_per_image": e["total_pj"],
            "energy_compute_pj": e["compute_pj"],
            "energy_dram_pj": e["dram_pj"],
            "frozen": bool(freeze),
            "compiles": engine.trace_count,
            "recompiles_after_warmup": engine.trace_count - traces_after_warmup,
        }
    dense_rec = record["policies"].get("dense", {})
    dense_e = dense_rec.get("energy_pj_per_image")
    dense_lat = dense_rec.get("latency_s_per_batch")
    if dense_e:
        for name, rec in record["policies"].items():
            rec["energy_vs_dense"] = rec["energy_pj_per_image"] / dense_e
            rec["latency_vs_dense"] = rec["latency_s_per_batch"] / dense_lat
    if "shiftadd" in record["policies"] and dense_lat:
        # The paper's headline crossover, tracked per PR (≤ 1.0 means the
        # reparameterized serving path beats dense at serve time).
        record["shiftadd_vs_dense_latency"] = (
            record["policies"]["shiftadd"]["latency_s_per_batch"] / dense_lat)
    return record
