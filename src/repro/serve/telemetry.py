"""Serving telemetry → router training: measured per-expert latencies.

The latency-aware load-balancing loss (core.losses, paper §4.2 Eq. 4) and
the static capacity split (core.moe_primitives) both consume per-expert
latencies α_i ∝ Lat_i. Until this module those came exclusively from the
analytic `core.energy` cost model; the serving stack, meanwhile, already
measures real per-bucket costs (the BENCH_traffic service models). This
closes the loop (ROADMAP item 3):

- `extract_expert_telemetry` probes each MoE expert STANDALONE on the exact
  per-expert dispatch segment shapes the frozen serving path feeds it
  (`MoEPrimitives._dispatch_tokens` static views), per bucket, interleaved
  round-robin with the warmup-discarding median every calibrator uses
  (`metrics.service_median_warm`).
- The result persists as a schema-versioned TELEMETRY_experts.json
  (`ExpertTelemetry.save`/`load`, same frozen-tuple + fail-open pattern as
  `kernels.autotune.TuneTable`): per-expert per-bucket wall seconds, the
  derived per-expert α latencies, and optionally the engine-level service
  medians they rode alongside (provenance).
- `apply_expert_latencies` drops the α latencies into every MoE feed as a
  drop-in replacement for the analytic `energy.expert_latencies` constants —
  `MoEPrimitives.latencies` is a setter that invalidates the memoized
  capacity plans, so rebuilt engines serve the measured split and
  `train.router_tune` fine-tunes the router against it.

Mode discipline (the TuneTable precedent): wall-clock α only on a TPU
backend (`mode="measured"`). Elsewhere `mode="model"` derives α from the
analytic model AT SERVING GEOMETRY (the per-image token count — the same
regime fix `MoEPrimitives.latencies_at` applies), the wall probes are still
recorded for visibility, and the meta says why: CPU/interpret wall times do
not rank TPU experts, and a CI gate fed noisy measured α would flap.
"""
from __future__ import annotations

import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy
from repro.core.moe_primitives import MoEPrimitives
from repro.serve.metrics import service_median_warm

SCHEMA_VERSION = 1


@dataclasses.dataclass(frozen=True)
class ExpertTelemetry:
    """Immutable per-expert serving-latency table.

    entries:         ((kind, ((bucket, seconds), ...)), ...) — measured
                     wall seconds of one MoE layer's expert segment, per
                     serving bucket (batch size).
    alpha_latencies: ((kind, seconds), ...) — THE α source: per-expert
                     latency at the per-image serving token count, either
                     measured (TPU) or analytic-at-serving-geometry (model
                     mode). `MoEPrimitives` consumes these verbatim.
    service_s:       ((bucket, seconds), ...) — engine-level calibrated
                     service medians the probes rode alongside (provenance;
                     empty when extracted outside a traffic sweep).
    meta:            ((key, value), ...) — mode/backend/reason/geometry.
    """

    entries: tuple = ()
    alpha_latencies: tuple = ()
    service_s: tuple = ()
    meta: tuple = ()

    @property
    def meta_dict(self) -> dict:
        return dict(self.meta)

    @property
    def mode(self) -> str:
        return self.meta_dict.get("mode", "model")

    def expert_latencies(self, kinds) -> list:
        """α latencies ordered for a feed's `expert_kinds` — the drop-in
        replacement for `energy.expert_latencies(...)`."""
        table = dict(self.alpha_latencies)
        return [float(table[k]) for k in kinds]

    def bucket_seconds(self, kind: str) -> dict:
        """{bucket: measured seconds} for one expert kind."""
        return {b: s for b, s in dict(self.entries).get(kind, ())}

    @staticmethod
    def from_dicts(entries: dict = None, alpha: dict = None,
                   service: dict = None, meta: dict = None) -> "ExpertTelemetry":
        ent = tuple(sorted(
            (kind, tuple(sorted((int(b), float(s)) for b, s in per.items())))
            for kind, per in (entries or {}).items()))
        alp = tuple(sorted((k, float(v)) for k, v in (alpha or {}).items()))
        svc = tuple(sorted((int(b), float(s))
                           for b, s in (service or {}).items()))
        def _freeze(v):
            return tuple(v) if isinstance(v, list) else v
        mt = tuple(sorted((k, _freeze(v)) for k, v in (meta or {}).items()))
        return ExpertTelemetry(entries=ent, alpha_latencies=alp,
                               service_s=svc, meta=mt)

    def to_json_dict(self) -> dict:
        def _thaw(v):
            return list(v) if isinstance(v, tuple) else v
        return {"schema": SCHEMA_VERSION,
                "meta": {k: _thaw(v) for k, v in self.meta},
                "alpha_latencies": {k: v for k, v in self.alpha_latencies},
                "service_s": {str(b): s for b, s in self.service_s},
                "entries": {kind: {str(b): s for b, s in per}
                            for kind, per in self.entries}}

    def save(self, path: str, report=None):
        doc = self.to_json_dict()
        if report is not None:
            doc["report"] = report
        with open(path, "w") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
            fh.write("\n")

    @staticmethod
    def load(path: str) -> "ExpertTelemetry":
        with open(path) as fh:
            doc = json.load(fh)
        assert doc.get("schema") == SCHEMA_VERSION, doc.get("schema")
        return ExpertTelemetry.from_dicts(doc.get("entries", {}),
                                          doc.get("alpha_latencies", {}),
                                          doc.get("service_s", {}),
                                          doc.get("meta", {}))


def load_telemetry(path: str):
    """ExpertTelemetry from a TELEMETRY_experts.json path, or None if
    absent/invalid — callers fall back to the analytic latencies rather
    than failing to boot (the TuneTable fail-open contract)."""
    try:
        return ExpertTelemetry.load(path)
    except (OSError, ValueError, AssertionError):
        return None


def _moe_feeds(model):
    """[(layer_index, block, feed)] for every MoEPrimitives feed."""
    return [(i, blk, blk.feed) for i, blk in enumerate(model.blocks)
            if isinstance(blk.feed, MoEPrimitives)]


def _feed_inputs(model, run_params, images, impl=None, tune=None):
    """Yield (block, block_params, feed_input) at each block, running the
    serving forward eagerly up to every feed — the activation shapes the
    frozen engine really dispatches."""
    dt = model.mc.activation_dtype
    x = model.patch_embed(run_params["patch_embed"],
                          model.patchify(jnp.asarray(images)).astype(dt))
    for blk, p in zip(model.blocks, run_params["blocks"]):
        h = blk.norm1(p["norm1"], x)
        mix = blk._infer_mixer(p, h, None, impl=impl, tune=tune)
        if blk.parallel:
            feed_in = h
            x = x + mix + blk._infer_feed(p, h, impl=impl, tune=tune)
        else:
            x = x + mix
            feed_in = blk.norm2(p["norm2"], x)
            x = x + blk._infer_feed(p, feed_in, impl=impl, tune=tune)
        yield blk, p, feed_in


def measure_token_share(model, run_params, images, impl=None, tune=None):
    """Fraction of tokens each expert KIND wins under serving routing.

    Replays the deterministic serving route (`group_rows` + clean-logit
    argmax — exactly `MoEPrimitives._route_infer`) at every MoE layer and
    aggregates argmax counts per expert kind. This is the paper's headline
    router behavior made observable: a router trained on real latencies
    should shift share toward the cheap shift/add expert. Returns
    {kind: share} (empty for models without MoE feeds).
    """
    from repro.nn.dispatch import group_rows

    counts = {}
    total = 0
    for blk, p, feed_in in _feed_inputs(model, run_params, images,
                                        impl=impl, tune=tune):
        feed = blk.feed
        if not isinstance(feed, MoEPrimitives):
            continue
        xg, _ = group_rows(feed_in, feed.d_model)
        top1, _ = feed._route_infer(p["feed"], xg)
        won = np.asarray(jax.nn.one_hot(top1, feed.n_experts,
                                        dtype=jnp.float32).sum((0, 1)))
        for i, kind in enumerate(feed.expert_kinds):
            counts[kind] = counts.get(kind, 0.0) + float(won[i])
        total += int(top1.size)
    if total == 0:
        return {}
    return {kind: c / total for kind, c in sorted(counts.items())}


def _probe_expert_seconds(feed, feed_params, feed_in, iters, impl, tune):
    """Interleaved wall-clock of each expert on its static dispatch segment.

    Each expert is jitted standalone on the exact (G, cap_e, d) view the
    serving dispatch hands it; iters+1 rounds, round 0 discarded
    (`service_median_warm`). Returns [seconds] ordered like feed.experts.
    """
    _, _, segments, _ = feed._dispatch_tokens(feed_params, feed_in)
    probes = []
    for i, (expert, seg) in enumerate(zip(feed.experts, segments)):
        ep = feed_params["experts"][i]
        if getattr(expert, "accepts_impl", False):
            fn = jax.jit(lambda s, e=expert, pp=ep:
                         e(pp, s, impl=impl, tune=tune))
        else:
            fn = jax.jit(lambda s, e=expert, pp=ep: e(pp, s))
        probes.append((fn, seg))
    samples = [[] for _ in probes]
    for _ in range(max(int(iters), 1) + 1):
        for i, (fn, seg) in enumerate(probes):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(seg))
            samples[i].append(time.perf_counter() - t0)
    return [service_median_warm(xs, warmup=1) for xs in samples]


def extract_expert_telemetry(model, params, *, buckets=None, impl=None,
                             tune=None, iters=5, measure=None,
                             service_model_s=None):
    """Probe a model's MoE experts at serving geometry → ExpertTelemetry.

    Freezes a DeployPlan for the serving token count (the PR-3 deploy
    freeze, so probes run the exact frozen segment programs), then times
    each expert of the FIRST MoE layer per bucket (layers share geometry;
    meta records how many layers the number stands for).

    measure=None → auto: α from wall clock only on a TPU backend; elsewhere
    α comes from the analytic model at the per-image serving token count
    (`mode="model"`, reason recorded) while the wall probes are still
    persisted for visibility. service_model_s ({bucket: seconds}, e.g. the
    shiftadd arm's calibrated service model) rides along as provenance.
    """
    from repro.serve.vision import DEFAULT_BUCKETS

    buckets = tuple(sorted(buckets or DEFAULT_BUCKETS))
    backend = jax.default_backend()
    if measure is None:
        measure = backend == "tpu"
    n_patches = model.cfg.n_patches
    plan = model.prepare_inference(params, impl=impl,
                                   token_counts=(n_patches,), tune=tune)
    run_params = plan.params
    feeds = _moe_feeds(model)
    if not feeds:
        raise ValueError("model has no MoEPrimitives feeds to probe")
    _, probe_blk, probe_feed = feeds[0]
    kinds = probe_feed.expert_kinds
    caps, _ = probe_feed.capacity_plan(n_patches)

    entries = {k: {} for k in kinds}
    shape = (model.cfg.image_size, model.cfg.image_size,
             model.cfg.in_channels)
    for b in buckets:
        imgs = jax.random.normal(jax.random.PRNGKey(17 + b), (b,) + shape)
        for blk, p, feed_in in _feed_inputs(model, run_params, imgs,
                                            impl=impl, tune=tune):
            if blk is probe_blk:
                secs = _probe_expert_seconds(probe_feed, p["feed"], feed_in,
                                             iters, impl, tune)
                for kind, s in zip(kinds, secs):
                    entries[kind][b] = s
                break

    if measure:
        # Per-token normalize at the largest bucket (most signal), then
        # express at the per-image token count — the α regime every consumer
        # (loss, capacity split) evaluates in. cap_e tokens per group row,
        # G = batch rows per probe.
        bmax = buckets[-1]
        alpha = {kind: (entries[kind][bmax] / (bmax * caps[i])) * n_patches
                 for i, kind in enumerate(kinds)}
        mode, reason = "measured", ("wall-clock expert segments on TPU, "
                                    "per-token normalized")
    else:
        analytic = energy.expert_latencies(n_patches, probe_feed.d_model,
                                           probe_feed.d_hidden, kinds)
        alpha = dict(zip(kinds, analytic))
        mode = "model"
        reason = (f"analytic cost model at serving geometry (backend="
                  f"{backend}; CPU/interpret wall times do not rank TPU "
                  "experts — probes recorded for visibility only)")
    meta = {"mode": mode, "backend": backend, "measured": bool(measure),
            "reason": reason, "buckets": list(buckets),
            "n_patches": n_patches, "d_model": probe_feed.d_model,
            "d_hidden": probe_feed.d_hidden, "expert_kinds": list(kinds),
            "capacities_per_image": list(caps),
            "capacity_factor": probe_feed.capacity_factor,
            "iters": int(iters), "n_moe_layers": len(feeds),
            "layers_measured": 1}
    return ExpertTelemetry.from_dicts(entries, alpha, service_model_s, meta)


def apply_expert_latencies(model, telemetry: ExpertTelemetry) -> int:
    """Drop the telemetry α latencies into every MoE feed of `model` — the
    drop-in replacement for the analytic `energy.expert_latencies` defaults.

    Returns the number of feeds updated. The `MoEPrimitives.latencies`
    setter invalidates each feed's memoized capacity plans, so engines and
    DeployPlans built BEFORE this call keep serving their old split:
    (re)build them afterwards.
    """
    feeds = _moe_feeds(model)
    if not feeds:
        raise ValueError("model has no MoEPrimitives feeds to update")
    for _, _, feed in feeds:
        feed.latencies = telemetry.expert_latencies(feed.expert_kinds)
    return len(feeds)
