"""Kernel autotune layer: search tile/packing/residency caps per kernel ×
serving geometry, persist the winners, and let the serving stack replay them.

The search space is seeded and pruned by the PR-6 contract table
(`repro.analysis.kernel_contracts`): every candidate is scored by the same
cell model the static analyzer emits — VMEM feasibility (double-buffered
blocks against the 16 MiB budget), roofline time max(t_compute, t_memory)
on the padded volumes, and pad-MAC waste — so a config the tuner picks is by
construction one the contract table classifies as launchable. On a TPU
backend the model-ranked shortlist is then *measured* through the real
`kernels.ops` wrappers (a one-entry tune table forces each candidate down
the exact serving path) and wall time picks the winner; off-TPU the model
ranking alone decides and the table records that reason — interpret-mode
timings would be meaningless.

What is tunable per kernel:

- shift_matmul / add_matmul: `bm`/`bn`/`bk` block caps (sublane / lane / K
  panel). The headline win at the serving geometry is `bk`: the untuned
  wrappers run the fixed K=512 panel, which pads the d_model=128 projections
  4× in K (the contract table's 0.75 pad-waste row).
- add_matmul_packed: `bm`/`bn` plus `bk8`, the code-packing panel width
  (packed rows of 8 logical K each; caps stay multiples of 16 so the x
  block's lane dim stays 128-aligned).
- linear_attention: `chunk`, the VMEM-residency chunk of the causal kernel.
- bidir_linear_attention: nothing — the fused kernel holds the whole
  sequence resident, so the tuner only records its VMEM feasibility (at
  n query and n_kv key/value rows, where a site gives `n_kv`).

Winning configs persist in TUNE_kernels.json (``TuneTable.save``/``load``)
keyed by exact kernel × geometry; `DeployPlan`/`BucketedViTEngine` thread
the loaded table to every `kernels.ops` call at freeze time, and a lookup
miss falls back to the module-default blocks — a stale table can never
break shapes (caps are re-resolved through the aligned-cover helpers) or
change semantics (blocks only partition the same padded dataflow).
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import time

SCHEMA_VERSION = 1

# Caps per kernel. Every value is a CAP, not a literal block: ops re-resolves
# through sublane_block/lane_block/kdim_block/packed_kdim_block covers, so any
# combination is shape-legal at any geometry; infeasible (VMEM) combinations
# are pruned by the contract-table oracle before ranking.
SEARCH_SPACE = {
    "shift_matmul": {"bm": (64, 128, 256), "bn": (128, 256),
                     "bk": (128, 256, 512)},
    "add_matmul": {"bm": (32, 64, 128), "bn": (128, 256),
                   "bk": (128, 256, 512)},
    "add_matmul_packed": {"bm": (32, 64, 128), "bn": (128, 256),
                          "bk8": (16, 32, 64)},
    "linear_attention": {"chunk": (64, 128, 256)},
    "bidir_linear_attention": {},
}

# Geometry keys each kernel's ops-wrapper lookup passes (must match the
# `_tuned(...)` call sites in kernels.ops exactly).
GEOMETRY_KEYS = {
    "shift_matmul": ("g", "m", "k", "n"),
    "add_matmul": ("g", "m", "k", "n"),
    "add_matmul_packed": ("g", "m", "k", "n"),
    "linear_attention": ("g", "n", "dk", "dv"),
}


def geometry_key(kernel: str, **geom) -> str:
    """Canonical string key for one kernel × exact geometry."""
    return "|".join([kernel] + [f"{k}={geom[k]}" for k in sorted(geom)])


@dataclasses.dataclass(frozen=True)
class TuneTable:
    """Immutable, hashable tune table.

    Hashability is load-bearing: the table rides in the `nondiff_argnums` of
    the `kernels.ops` custom-VJP wrappers, so jit caches key on it — two
    engines with different tables coexist without retrace collisions.

    entries: ((geometry_key, ((param, cap), ...)), ...) — sorted tuples.
    meta: ((key, value), ...) — provenance (backend, measured, reason, ...).
    """

    entries: tuple = ()
    meta: tuple = ()

    def __post_init__(self):
        # Derived lookup index; not a dataclass field, so hash/eq stay on the
        # canonical tuples.
        object.__setattr__(
            self, "_index", {k: dict(v) for k, v in self.entries})

    def lookup(self, kernel: str, **geom):
        """Tuned caps dict for this exact geometry, or None (→ defaults)."""
        return self._index.get(geometry_key(kernel, **geom))

    def __len__(self):
        return len(self.entries)

    @property
    def meta_dict(self) -> dict:
        return dict(self.meta)

    @staticmethod
    def from_dicts(entries: dict, meta: dict = None) -> "TuneTable":
        def _freeze(v):
            return tuple(v) if isinstance(v, list) else v

        ent = tuple(sorted(
            (k, tuple(sorted((p, int(c)) for p, c in v.items())))
            for k, v in entries.items()))
        mt = tuple(sorted((k, _freeze(v)) for k, v in (meta or {}).items()))
        return TuneTable(entries=ent, meta=mt)

    def to_json_dict(self) -> dict:
        def _thaw(v):
            return list(v) if isinstance(v, tuple) else v

        return {"schema": SCHEMA_VERSION,
                "meta": {k: _thaw(v) for k, v in self.meta},
                "entries": {k: dict(v) for k, v in self.entries}}

    def save(self, path: str, report=None):
        doc = self.to_json_dict()
        if report is not None:
            doc["report"] = report
        with open(path, "w") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
            fh.write("\n")

    @staticmethod
    def load(path: str) -> "TuneTable":
        with open(path) as fh:
            doc = json.load(fh)
        assert doc.get("schema") == SCHEMA_VERSION, doc.get("schema")
        return TuneTable.from_dicts(doc.get("entries", {}),
                                    doc.get("meta", {}))


def candidates(kernel: str) -> list:
    """Every cap combination in the kernel's search space (dicts; possibly
    the empty dict for feasibility-only kernels)."""
    space = SEARCH_SPACE.get(kernel, {})
    keys = sorted(space)
    if not keys:
        return [{}]
    return [dict(zip(keys, vals))
            for vals in itertools.product(*(space[k] for k in keys))]


def _site_geometry(spec: dict) -> dict:
    geom = {k: spec[k] for k in GEOMETRY_KEYS[spec["kernel"]]}
    if spec["kernel"] == "add_matmul_packed":
        # The packed wrapper's lookup sees x.shape[2] == 8 * packed-rows, and
        # pack_bits requires the caller to pad K to a multiple of 8 first —
        # so at e.g. the 196-token serving site the wrapper looks up k=200,
        # never k=196. Key the table at the k the lookup will actually carry
        # (the contract cell keeps the true k for honest pad-waste).
        geom["k"] = -(-geom["k"] // 8) * 8
    return geom


def rank_candidates(spec: dict, bucket: int) -> list:
    """Model-rank the feasible tile configs for one serving site.

    Returns [(caps, cell)] best-first: VMEM-overflowing configs are pruned,
    the rest sort by roofline time max(t_compute, t_memory), tie-broken by
    pad-MAC waste then VMEM pressure. Candidates whose caps resolve to the
    same launched blocks are deduplicated (first = best kept)."""
    from repro.analysis import kernel_contracts as kc

    scored = []
    for caps in candidates(spec["kernel"]):
        cell = kc.cell_for_site(spec, bucket, blocks=caps or None)
        if cell.classification == "vmem_overflow":
            continue
        cost = (max(cell.t_compute_s, cell.t_memory_s), cell.pad_mac_waste,
                cell.vmem_frac)
        scored.append((cost, caps, cell))
    scored.sort(key=lambda t: t[0])
    seen, uniq = set(), []
    for _, caps, cell in scored:
        resolved = tuple(sorted(cell.blocks.items()))
        if resolved in seen:
            continue
        seen.add(resolved)
        uniq.append((caps, cell))
    return uniq


def _measure_site(spec: dict, caps: dict, iters: int = 20) -> float:
    """Median wall time of one candidate through the REAL serving path: a
    one-entry tune table forces `caps` down the exact `kernels.ops` wrapper
    the engine calls. TPU only — interpret timings are meaningless."""
    import jax
    import jax.numpy as jnp

    from repro.core import quant
    from repro.kernels import ops
    from repro.kernels.add_matmul_packed import pack_bits

    kernel = spec["kernel"]
    table = TuneTable.from_dicts(
        {geometry_key(kernel, **_site_geometry(spec)): caps})
    key = jax.random.PRNGKey(0)
    if kernel == "shift_matmul":
        x = jax.random.normal(key, (spec["m"], spec["k"]))
        w = quant.pack_from_dense(
            0.05 * jax.random.normal(key, (spec["k"], spec["n"])))
        fn = lambda: ops.shift_matmul(x, w, "pallas", table)
    elif kernel == "add_matmul":
        x = jax.random.normal(key, (spec["g"], spec["m"], spec["k"]))
        b = (jax.random.randint(key, (spec["g"], spec["k"], spec["n"]), 0, 2,
                                jnp.int8) * 2 - 1).astype(jnp.int8)
        fn = lambda: ops.add_matmul(x, b, "pallas", table)
    elif kernel == "add_matmul_packed":
        # pack_bits requires 8-aligned K; drive the wrapper at the padded K
        # it will see in serving (matches the table key — see _site_geometry).
        kp = -(-spec["k"] // 8) * 8
        x = jax.random.normal(key, (spec["g"], spec["m"], kp))
        b = (jax.random.randint(key, (spec["g"], kp, spec["n"]), 0, 2,
                                jnp.int8) * 2 - 1).astype(jnp.int8)
        packed = pack_bits(b)
        fn = lambda: ops.add_matmul_bitpacked(x, packed, "pallas", table)
    else:
        assert kernel == "linear_attention", kernel
        g, n, dk, dv = spec["g"], spec["n"], spec["dk"], spec["dv"]
        q = jax.random.normal(key, (g, 1, n, dk))
        k = jax.random.normal(key, (g, 1, n, dk))
        v = jax.random.normal(key, (g, 1, n, dv))
        fn = lambda: ops.binary_linear_attention_fused(
            q, k, v, impl="pallas", tune=table)
    jax.block_until_ready(fn())                     # compile
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def autotune(base_cfg=None, buckets=None, measure=None, iters=20,
             shortlist=6):
    """Search every serving site × bucket; return (TuneTable, report rows).

    measure=None → auto: measure on TPU, model-rank elsewhere (the recorded
    `reason` says which). `shortlist` caps how many model-ranked candidates
    get wall-clock measured per site."""
    import jax

    from repro.analysis import kernel_contracts as kc
    from repro.nn.vit import ViTConfig
    from repro.serve.vision import DEFAULT_BUCKETS

    cfg = base_cfg or ViTConfig()
    buckets = tuple(buckets or DEFAULT_BUCKETS)
    backend = jax.default_backend()
    if measure is None:
        measure = backend == "tpu"
    entries, report = {}, []
    for b in buckets:
        for spec in kc.serving_sites(cfg, b):
            kernel = spec["kernel"]
            if not SEARCH_SPACE.get(kernel):
                cell = kc.cell_for_site(spec, b)
                report.append({
                    "kernel": kernel, "site": spec["site"], "bucket": b,
                    "geometry": cell.geometry, "winner": None,
                    "classification": cell.classification,
                    "note": "feasibility-only (no block tunables)"})
                continue
            geom = _site_geometry(spec)
            key = geometry_key(kernel, **geom)
            default_cell = kc.cell_for_site(spec, b)
            ranked = rank_candidates(spec, b)
            if not ranked:
                report.append({
                    "kernel": kernel, "site": spec["site"], "bucket": b,
                    "geometry": geom, "winner": None,
                    "classification": "vmem_overflow",
                    "note": "no feasible candidate in the search space"})
                continue
            measured_s = None
            if measure:
                timed = sorted(
                    (_measure_site(spec, caps, iters=iters), caps, cell)
                    for caps, cell in ranked[:shortlist])
                measured_s, caps, cell = timed[0]
            else:
                caps, cell = ranked[0]
            if key not in entries:       # same geometry can recur at bucket b
                entries[key] = caps
            report.append({
                "kernel": kernel, "site": spec["site"], "bucket": b,
                "geometry": geom, "winner": caps,
                "winner_blocks": cell.blocks,
                "default_blocks": default_cell.blocks,
                "classification": cell.classification,
                "t_model_s": max(cell.t_compute_s, cell.t_memory_s),
                "t_model_default_s": max(default_cell.t_compute_s,
                                         default_cell.t_memory_s),
                "pad_mac_waste": cell.pad_mac_waste,
                "pad_mac_waste_default": default_cell.pad_mac_waste,
                "measured_s": measured_s,
                "n_candidates": len(ranked)})
    reason = ("wall-clock measured through kernels.ops on TPU" if measure
              else f"model-ranked only (backend={backend}; interpret-mode "
                   "timings are not meaningful)")
    meta = {"backend": backend, "measured": bool(measure), "reason": reason,
            "buckets": list(buckets), "image_size": cfg.image_size,
            "d_model": cfg.d_model, "n_layers": cfg.n_layers,
            "n_heads": cfg.n_heads, "d_ff": cfg.d_ff}
    return TuneTable.from_dicts(entries, meta), report


def load_table(path: str):
    """TuneTable from a TUNE_kernels.json path, or None if absent/invalid —
    serving falls back to default blocks rather than failing to boot."""
    try:
        return TuneTable.load(path)
    except (OSError, ValueError, AssertionError):
        return None
