"""Pass 2: Pallas kernel contract checker — the kernel × geometry matrix.

For every Pallas kernel in `repro.kernels` at every `DEFAULT_BUCKETS` batch
geometry of the serving ViT, compute the contract cell the ROADMAP autotune
layer needs as its search-space validator:

- **block geometry**: the exact (bm, bn, bk / chunk) the `kernels.ops`
  wrappers would pick (shared helpers `ops.sublane_block`/`ops.lane_block` —
  the table models the code, it does not re-guess it), the resulting grid,
  and how much each dimension is padded.
- **classification**: `tile_aligned` (no padding anywhere), `pad_and_slice`
  (the kernel zero-pads to the tile grid and slices back — correct but
  wasted MACs/bandwidth; the expected state at the CIFAR-scale geometry,
  e.g. shift_matmul pads K 128→512), or `vmem_overflow` (the per-grid-step
  working set exceeds the VMEM budget — the kernel will not fit; the ONLY
  classification that is a Finding, rule KC001).
- **roofline terms**: padded-volume compute time against the int8/bf16 MXU
  peak and HBM traffic time, using the same peak/bandwidth constants as
  `benchmarks/roofline.py` (`repro.core.energy`), plus the fraction of MACs
  spent on padding — the number the autotune layer minimizes.

VMEM accounting: every in/out BlockSpec block counts TWICE (Pallas
double-buffers pipelined blocks), scratch once, against a 16 MiB/core
budget (the v4/v5 figure from the Pallas TPU guide).
"""
from __future__ import annotations

import dataclasses
import os
import re

from repro.analysis.findings import Finding
from repro.core.energy import HBM_BW, PEAK_FLOPS_BF16, PEAK_OPS_INT8

RULES = {"KC001": "kernel working set exceeds the VMEM budget"}

VMEM_BUDGET_BYTES = 16 * 2 ** 20
F32 = 4  # activation / accumulator bytes


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclasses.dataclass
class Cell:
    """One kernel × site × bucket contract entry (a row of the table)."""
    kernel: str
    site: str
    bucket: int
    geometry: dict         # true problem sizes
    blocks: dict           # chosen block sizes
    grid: tuple
    padded: dict           # padded problem sizes
    classification: str    # tile_aligned | pad_and_slice | vmem_overflow
    vmem_bytes: int
    vmem_frac: float
    pad_mac_waste: float   # fraction of executed MACs that hit padding
    t_compute_s: float
    t_memory_s: float
    bound: str             # compute | memory

    def row(self) -> dict:
        return dataclasses.asdict(self)


def _finish(kernel, site, bucket, geometry, blocks, grid, padded, vmem,
            flops_padded, flops_true, hbm_bytes, peak):
    t_c = flops_padded / peak
    t_m = hbm_bytes / HBM_BW
    overflow = vmem > VMEM_BUDGET_BYTES
    aligned = all(padded[k] == geometry[k] for k in padded)
    return Cell(
        kernel=kernel, site=site, bucket=bucket, geometry=geometry,
        blocks=blocks, grid=tuple(grid), padded=padded,
        classification=("vmem_overflow" if overflow
                        else "tile_aligned" if aligned else "pad_and_slice"),
        vmem_bytes=int(vmem), vmem_frac=vmem / VMEM_BUDGET_BYTES,
        pad_mac_waste=1.0 - flops_true / max(flops_padded, 1.0),
        t_compute_s=t_c, t_memory_s=t_m,
        bound="compute" if t_c >= t_m else "memory")


# ---------------------------------------------------------------------------
# Per-kernel cell models (mirroring each wrapper's block selection exactly)
# ---------------------------------------------------------------------------

def matmul_cell(kernel, site, bucket, g, m, k, n, *, w_bytes, adapt_bn,
                packed_k=False, blocks=None):
    """shift_matmul / add_matmul / add_matmul_packed share one dataflow:
    grid (G, M/bm, N/bn, K/bk) with an (bm, bn) f32 VMEM accumulator.

    blocks=None models the wrappers' UNTUNED defaults; a dict of tuned caps
    (bm/bn/bk or bk8) replays exactly the resolution `kernels.ops` applies to
    a TuneTable hit (`sublane_block`/`lane_block`/`kdim_block` covers), so
    the autotuner's cost oracle and the launched grid can never diverge."""
    from repro.kernels import add_matmul as _addmm
    from repro.kernels import add_matmul_packed as _pk
    from repro.kernels import ops
    from repro.kernels import shift_matmul as _shiftmm

    mod = {"shift_matmul": _shiftmm, "add_matmul": _addmm,
           "add_matmul_packed": _pk}[kernel]
    if blocks is None:
        bm = ops.sublane_block(m, mod.BM)
        bn = ops.lane_block(n, mod.BN) if adapt_bn else mod.BN
        bk = mod.BK8 * 8 if packed_k else mod.BK
    else:
        bm = ops.sublane_block(m, blocks.get("bm", mod.BM))
        bn = ops.lane_block(n, blocks.get("bn", mod.BN))
        if packed_k:
            bk = 8 * ops.packed_kdim_block(-(-k // 8),
                                           blocks.get("bk8", mod.BK8))
        else:
            bk = ops.kdim_block(k, blocks.get("bk", mod.BK))
    mp, kp, np_ = _ceil_to(m, bm), _ceil_to(k, bk), _ceil_to(n, bn)
    grid_mnk = (mp // bm, np_ // bn, kp // bk)
    grid = grid_mnk if g == 1 and kernel == "shift_matmul" else (g,) + grid_mnk
    # Weight-block bytes: packed kernels hold K/8 rows per logical K block.
    wk_rows = bk // 8 if packed_k else bk
    vmem = (2 * (bm * bk * F32 + wk_rows * bn * w_bytes + bm * bn * F32)
            + bm * bn * F32)
    # HBM traffic: x re-read per N-tile, weights re-read per M-tile, out once.
    hbm = g * (mp * kp * F32 * grid_mnk[1]
               + (kp // 8 if packed_k else kp) * np_ * w_bytes * grid_mnk[0]
               + mp * np_ * F32)
    return _finish(kernel, site, bucket,
                   {"g": g, "m": m, "k": k, "n": n},
                   {"bm": bm, "bn": bn, "bk": bk},
                   grid, {"m": mp, "k": kp, "n": np_},
                   vmem, 2.0 * g * mp * kp * np_, 2.0 * g * m * k * n, hbm,
                   PEAK_OPS_INT8)


def linear_attention_cell(bucket, g, n, dk, dv, *, blocks=None):
    """Chunked causal kernel: grid (G, N/chunk); carry (dk, dv) in VMEM.

    blocks={"chunk": c} overrides the VMEM-residency chunk the same way a
    TuneTable hit does in `ops.binary_linear_attention_fused`.

    The MAC law counts every contraction the kernel executes per chunk — the
    inter-chunk terms bq@KV and bkᵀ@v AND the intra-chunk causal pair
    s = bq@bkᵀ, s@v (each chunk² · head-dim). The old law modeled only the
    inter-chunk 4·n·dk·dv terms, so at the 196-token serving geometry
    (chunk = n) it under-counted the executed MACs by more than half and the
    pad-waste the tuner minimizes drifted from what the wrapper launches."""
    from repro.kernels import linear_attention as _linattn

    chunk = min((blocks or {}).get("chunk", _linattn.CHUNK), n)
    dkp, dvp = _ceil_to(dk, 128), _ceil_to(dv, 128)
    np_ = _ceil_to(n, chunk)
    grid = (g, np_ // chunk)
    vmem = (2 * (2 * chunk * dkp * F32 + chunk * dvp * F32   # q, k | v
                 + chunk * dvp * F32)                        # out
            + (dkp * dvp + dkp + dvp) * F32                  # carry scratch
            + chunk * chunk * F32)                           # intra-chunk S
    hbm = g * ((2 * np_ * dkp + 2 * np_ * dvp) * F32)

    def flops(nn, c, a, b):
        # 2 flops/MAC × (bq@KV + bkᵀ@v: nn·a·b each; bq@bkᵀ: nn·c·a; s@v:
        # nn·c·b) summed over the nn/c chunk steps.
        return 2.0 * g * (2.0 * nn * a * b + nn * c * (a + b))

    return _finish("linear_attention", "causal_attn", bucket,
                   {"g": g, "n": n, "dk": dk, "dv": dv},
                   {"chunk": chunk},
                   grid, {"n": np_, "dk": dkp, "dv": dvp},
                   vmem, flops(np_, chunk, dkp, dvp),
                   flops(n, min(chunk, n), dk, dv), hbm,
                   PEAK_FLOPS_BF16)


def bidir_attention_cell(bucket, g, n, dk, dv, n_kv=None):
    """Fused bidirectional kernel: whole sequence per grid step in VMEM.
    q and out hold n rows, k and v n_kv (default n: self-attention; fewer
    under PVT's spatial-reduction attention)."""
    from repro.kernels import bidir_linear_attention as _bidir

    n_kv = n if n_kv is None else n_kv
    dkp, dvp = _ceil_to(dk, 128), _ceil_to(dv, 128)
    np_, nkp = _ceil_to(n, 8), _ceil_to(n_kv, 8)
    rows = (np_ + nkp) * dkp + (nkp + np_) * dvp     # q, k | v, out
    vmem = 2 * rows * F32
    over_cap = max(np_, nkp) > _bidir.MAX_FUSED_N
    geometry = {"g": g, "n": n, "dk": dk, "dv": dv}
    if n_kv != n:
        geometry["n_kv"] = n_kv
    cell = _finish("bidir_linear_attention", "encoder_attn", bucket,
                   geometry, {"n_block": np_},
                   (g,), {"n": np_, "dk": dkp, "dv": dvp},
                   vmem, 2.0 * g * (np_ + nkp) * dkp * dvp,
                   2.0 * g * (n + n_kv) * dk * dv,
                   g * rows * F32,
                   PEAK_FLOPS_BF16)
    if over_cap:   # the kernel refuses these shapes outright
        cell.classification = "vmem_overflow"
    return cell


# ---------------------------------------------------------------------------
# The serving geometry: ViTConfig × DEFAULT_BUCKETS
# ---------------------------------------------------------------------------

MATMUL_KERNELS = ("shift_matmul", "add_matmul", "add_matmul_packed")


def serving_sites(cfg, b) -> list:
    """Every kernel's serving call-site geometry at batch-bucket b, as plain
    dicts — the single source both `cells_for_bucket` (the contract table)
    and `kernels.autotune` (the search-space enumerator) iterate, so the
    tuner can never tune a geometry the table doesn't model.

    Site geometries come from the ShiftAddViT serving path: projections see
    (B·N_patches, d) token matrices; the binary attention matmuls group over
    B·heads with per-head (dh) feature dims; MoE experts see at most the
    full token load (the per-image capacity split only shrinks M).
    """
    n, d, f, h = cfg.n_patches, cfg.d_model, cfg.d_ff, cfg.n_heads
    dh = d // h
    toks = b * n
    return [
        dict(kernel="shift_matmul", site="qkvo_proj", g=1, m=toks, k=d, n=d,
             w_bytes=1, adapt_bn=False),
        dict(kernel="shift_matmul", site="moe_shift_up", g=1, m=toks, k=d,
             n=f, w_bytes=1, adapt_bn=False),
        dict(kernel="shift_matmul", site="moe_shift_down", g=1, m=toks, k=f,
             n=d, w_bytes=1, adapt_bn=False),
        dict(kernel="add_matmul", site="ktv", g=b * h, m=dh, k=n, n=dh,
             w_bytes=1, adapt_bn=True),
        dict(kernel="add_matmul", site="q_ktv", g=b * h, m=n, k=dh, n=dh,
             w_bytes=1, adapt_bn=True),
        dict(kernel="add_matmul_packed", site="ktv", g=b * h, m=dh, k=n,
             n=dh, w_bytes=1, adapt_bn=True, packed_k=True),
        dict(kernel="add_matmul_packed", site="q_ktv", g=b * h, m=n, k=dh,
             n=dh, w_bytes=1, adapt_bn=True, packed_k=True),
        dict(kernel="linear_attention", site="causal_attn", g=b * h, n=n,
             dk=dh, dv=dh),
        dict(kernel="bidir_linear_attention", site="encoder_attn", g=b * h,
             n=n, dk=dh, dv=dh),
    ]


def cell_for_site(site_spec: dict, bucket: int, blocks=None) -> Cell:
    """One `serving_sites` entry → its contract Cell, optionally under tuned
    block caps (the autotuner's cost oracle)."""
    s = dict(site_spec)
    kernel, site = s.pop("kernel"), s.pop("site")
    if kernel in MATMUL_KERNELS:
        return matmul_cell(kernel, site, bucket, s["g"], s["m"], s["k"],
                           s["n"], w_bytes=s["w_bytes"],
                           adapt_bn=s["adapt_bn"],
                           packed_k=s.get("packed_k", False), blocks=blocks)
    if kernel == "linear_attention":
        return linear_attention_cell(bucket, s["g"], s["n"], s["dk"],
                                     s["dv"], blocks=blocks)
    assert kernel == "bidir_linear_attention", kernel
    return bidir_attention_cell(bucket, s["g"], s["n"], s["dk"], s["dv"],
                                s.get("n_kv"))


def cells_for_bucket(cfg, b) -> list:
    """Every kernel's serving call sites at batch-bucket b (untuned blocks)."""
    return [cell_for_site(spec, b) for spec in serving_sites(cfg, b)]


def pallas_kernel_names() -> set:
    """Module names under repro.kernels that define a pallas_call — the
    coverage ground truth the tests hold the table against."""
    import repro.kernels as pkg

    root = os.path.dirname(pkg.__file__)
    names = set()
    for fname in sorted(os.listdir(root)):
        if not fname.endswith(".py"):
            continue
        with open(os.path.join(root, fname)) as fh:
            if re.search(r"\bpl\.pallas_call\b", fh.read()):
                names.add(fname[:-3])
    return names


def run(base_cfg=None, buckets=None):
    """The full pass → (findings, table-rows).

    Only `vmem_overflow` cells are findings (KC001): pad_and_slice is the
    documented slow path, not a contract violation — the table records it so
    the autotune layer can hunt aligned geometries.
    """
    from repro.nn.vit import ViTConfig
    from repro.serve.vision import DEFAULT_BUCKETS

    cfg = base_cfg or ViTConfig()
    buckets = tuple(buckets or DEFAULT_BUCKETS)
    rows, findings = [], []
    for b in buckets:
        for cell in cells_for_bucket(cfg, b):
            rows.append(cell)
            if cell.classification == "vmem_overflow":
                findings.append(Finding(
                    rule="KC001", pass_name="kernels",
                    where=f"{cell.kernel}/{cell.site}/bucket={b}",
                    message=(f"working set {cell.vmem_bytes / 2**20:.1f} MiB "
                             f"exceeds the {VMEM_BUDGET_BYTES / 2**20:.0f} "
                             f"MiB VMEM budget (blocks {cell.blocks})")))
    covered = {c.kernel for c in rows}
    missing = pallas_kernel_names() - covered
    for name in sorted(missing):
        findings.append(Finding(
            rule="KC001", pass_name="kernels", where=f"kernels/{name}",
            message="Pallas kernel has no contract-table entry — add its "
                    "cell model to analysis.kernel_contracts"))
    return findings, rows


def format_table(rows) -> str:
    """Human-readable kernel × bucket grid (one line per cell)."""
    head = (f"{'kernel':<22} {'site':<15} {'bucket':>6} {'class':<14} "
            f"{'vmem':>9} {'waste':>6} {'bound':>8}")
    lines = [head, "-" * len(head)]
    for c in rows:
        lines.append(
            f"{c.kernel:<22} {c.site:<15} {c.bucket:>6} "
            f"{c.classification:<14} {c.vmem_bytes / 2**20:>7.2f}Mi "
            f"{c.pad_mac_waste:>5.0%} {c.bound:>8}")
    return "\n".join(lines)
