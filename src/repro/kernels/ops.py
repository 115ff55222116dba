"""Public jit'd wrappers around the Pallas kernels.

Implementation selection (per-call `impl=` or process-wide default):

- "pallas"    — real TPU kernel (the deployment path).
- "interpret" — Pallas interpret mode: kernel body executed on CPU; used by
                the correctness tests against the ref.py oracles.
- "xla"       — semantics-identical pure-XLA twin with the *same storage
                format* (packed int8 weights, int8 binary operands). This is
                what the CPU dry-run lowers, so the roofline sees the real
                HBM layout (1 B/weight) without TPU codegen.

Default is "pallas" on TPU and "xla" elsewhere. Wrappers pick shape-adapted
block sizes and define custom VJPs (gradients flow to activations only —
packed operands are frozen deployment artifacts). Padding to tile multiples
lives in the kernels themselves (pad-and-slice), so arbitrary shapes — e.g.
the 197-token DeiT sequence — are first-class on every path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quant import po2_weight_from_packed
from repro.kernels import add_matmul as _addmm
from repro.kernels import linear_attention as _linattn
from repro.kernels import ref as _ref
from repro.kernels import shift_matmul as _shiftmm

_IMPL_OVERRIDE = None


def default_impl() -> str:
    """Implementation used by `impl=None` call sites: the explicit override
    (if `set_default_impl` was called) else the live backend — "pallas" on
    TPU, "xla" elsewhere. Deliberately NOT memoized: the old first-call cache
    meant an early import could pin the wrong backend for the whole process.
    Serving entry points (engine → blocks → ops) thread `impl` explicitly and
    never consult this; it exists for ad-hoc/test call sites only."""
    if _IMPL_OVERRIDE is not None:
        return _IMPL_OVERRIDE
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def set_default_impl(impl):
    """Set (or with None, clear) the process-wide `impl=None` fallback.

    This is a blunt instrument kept for ad-hoc experiments; the benchmark and
    serve CLIs pass `impl` explicitly down the engine stack instead, so two
    engines with different impls can coexist in one process."""
    assert impl is None or impl in ("pallas", "interpret", "xla")
    global _IMPL_OVERRIDE
    _IMPL_OVERRIDE = impl


from repro.kernels.padding import pad_to_multiple as _pad_to


def sublane_block(m: int, cap: int) -> int:
    """Shape-adapted M-block: the kernel's block size, shrunk to the
    sublane-aligned (multiple-of-8) cover of a small M. Single source of
    truth for the wrappers below AND repro.analysis.kernel_contracts (the
    contract table must model exactly the block geometry the wrappers pick)."""
    return min(cap, -(-m // 8) * 8)


def lane_block(n: int, cap: int) -> int:
    """Shape-adapted N-block: lane-aligned (multiple-of-128) cover of N."""
    return min(cap, -(-n // 128) * 128)


def kdim_block(k: int, cap: int) -> int:
    """Shape-adapted K-block. The K panel is the x-operand block's lane
    dimension, so caps must stay multiples of 128 — same law as lane_block,
    split out so tuned caps document which axis they constrain."""
    return min(cap, -(-k // 128) * 128)


def packed_kdim_block(k8: int, cap: int) -> int:
    """Shape-adapted packed-K block (add_matmul_bitpacked): k8 counts PACKED
    rows (8 logical K per row). The x block's lane dim is 8*bk8, so caps must
    be multiples of 16 (→ 128 logical K)."""
    return min(cap, -(-k8 // 16) * 16)


def _tuned(tune, kernel, **geom):
    """Tuned block caps for one kernel × geometry, or None for the module
    defaults. `tune` is anything with `.lookup(kernel, **geom) -> dict|None`
    (kernels.autotune.TuneTable); ops only duck-types it so the dependency
    stays one-way. Tuned caps are resolved through the aligned-cover helpers
    above, so a table entry can never produce an illegal block shape."""
    if tune is None:
        return None
    return tune.lookup(kernel, **geom)


# ---------------------------------------------------------------------------
# shift_matmul: y = x @ (s * 2^P), packed int8 weights
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def shift_matmul(x, w_packed, impl=None, tune=None):
    """x: (..., K) float; w_packed: (K, N) int8 → (..., N).

    tune: optional TuneTable (hashable — it rides in nondiff_argnums) whose
    entry for this geometry overrides the module-default block caps."""
    return _shift_matmul_fwd_impl(x, w_packed, impl, tune)


def _shift_matmul_fwd_impl(x, w_packed, impl, tune=None):
    impl = impl or default_impl()
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    if impl == "xla":
        y = _ref.shift_matmul_ref(x2, w_packed)
    else:
        m = x2.shape[0]
        n = w_packed.shape[-1]
        cfg = _tuned(tune, "shift_matmul", g=1, m=m, k=k, n=n)
        if cfg is None:
            # Untuned defaults: adapt only the M block (the contract table
            # replays exactly this law — see kernel_contracts.matmul_cell).
            bm, bn, bk = sublane_block(m, _shiftmm.BM), _shiftmm.BN, _shiftmm.BK
        else:
            bm = sublane_block(m, cfg.get("bm", _shiftmm.BM))
            bn = lane_block(n, cfg.get("bn", _shiftmm.BN))
            bk = kdim_block(k, cfg.get("bk", _shiftmm.BK))
        y = _shiftmm.shift_matmul_pallas(
            x2, w_packed, bm=bm, bn=bn, bk=bk, interpret=(impl == "interpret"))
    return y.reshape(*lead, -1)


def _shift_matmul_vjp_fwd(x, w_packed, impl, tune):
    return _shift_matmul_fwd_impl(x, w_packed, impl, tune), (w_packed,)


def _shift_matmul_vjp_bwd(impl, tune, res, g):
    (w_packed,) = res
    w = po2_weight_from_packed(w_packed, jnp.float32)
    gx = jnp.einsum("...n,kn->...k", g.astype(jnp.float32), w).astype(g.dtype)
    return (gx, None)


shift_matmul.defvjp(_shift_matmul_vjp_fwd, _shift_matmul_vjp_bwd)


# ---------------------------------------------------------------------------
# add_matmul: y = x @ b, b int8 in {-1, 0, +1}
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def add_matmul(x, b, impl=None, tune=None):
    """x: (G, M, K) float; b: (G, K, N) int8 → (G, M, N)."""
    return _add_matmul_fwd_impl(x, b, impl, tune)


def _add_matmul_fwd_impl(x, b, impl, tune=None):
    impl = impl or default_impl()
    if impl == "xla":
        return _ref.add_matmul_ref(x, b)
    g, m, k = x.shape
    n = b.shape[-1]
    cfg = _tuned(tune, "add_matmul", g=g, m=m, k=k, n=n) or {}
    bm = sublane_block(m, cfg.get("bm", _addmm.BM))
    bn = lane_block(n, cfg.get("bn", _addmm.BN))
    bk = kdim_block(k, cfg.get("bk", _addmm.BK)) if cfg else _addmm.BK
    return _addmm.add_matmul_pallas(x, b, bm=bm, bn=bn, bk=bk,
                                    interpret=(impl == "interpret"))


def _add_matmul_vjp_fwd(x, b, impl, tune):
    return _add_matmul_fwd_impl(x, b, impl, tune), (b,)


def _add_matmul_vjp_bwd(impl, tune, res, g):
    (b,) = res
    gx = jnp.einsum("gmn,gkn->gmk", g.astype(jnp.float32),
                    b.astype(jnp.float32)).astype(g.dtype)
    return (gx, None)


add_matmul.defvjp(_add_matmul_vjp_fwd, _add_matmul_vjp_bwd)


# ---------------------------------------------------------------------------
# bit-packed add_matmul (beyond-paper: 1 bit/element binary operand)
# ---------------------------------------------------------------------------

def add_matmul_bitpacked(x, packed, impl=None, tune=None):
    """x: (G, M, K) float; packed: (G, K//8, N) uint8 ±1 codes → (G, M, N).

    The tunable `bk8` is the code-packing panel width: how many PACKED rows
    (8 logical K each) one grid step consumes."""
    from repro.kernels import add_matmul_packed as _pk

    impl = impl or default_impl()
    if impl == "xla":
        b = _pk.unpack_bits(packed, jnp.float32)
        return _ref.add_matmul_ref(x, b)
    g, m, k = x.shape
    k8 = packed.shape[1]
    n = packed.shape[-1]
    cfg = _tuned(tune, "add_matmul_packed", g=g, m=m, k=k, n=n) or {}
    bm = sublane_block(m, cfg.get("bm", _pk.BM))
    bn = lane_block(n, cfg.get("bn", _pk.BN))
    bk8 = packed_kdim_block(k8, cfg.get("bk8", _pk.BK8)) if cfg else _pk.BK8
    return _pk.add_matmul_packed_pallas(x, packed, bm=bm, bn=bn, bk8=bk8,
                                        interpret=(impl == "interpret"))


# ---------------------------------------------------------------------------
# fused bidirectional (encoder) binary linear attention
# ---------------------------------------------------------------------------

def binary_linear_attention_bidir(q, k, v, *, impl=None, tune=None):
    """q: (B, H, Nq, Dk); k: (B, H, Nkv, Dk); v: (B, H, Nkv, Dv) → (B, H, Nq,
    Dv). Non-causal — the ViT/encoder serving form of the Hamming-kernel
    attention. Nkv may differ from Nq (PVT's spatial-reduction attention:
    keys and values over a reduced token set); each is padded and masked on
    its own.

    Inference-only (no VJP; training uses repro.core.add_attention, whose STE
    machinery this path exists to skip). impl="xla" runs the sign-trick twin;
    pallas/interpret run the fused single-pass kernel with codes in VMEM.
    `tune` is accepted for call-site uniformity: the fused kernel holds the
    whole sequence resident, so it has no block tunables — the autotuner only
    records its VMEM feasibility.
    """
    del tune  # feasibility-gated, not block-tunable (see docstring)
    from repro.kernels import bidir_linear_attention as _bidir

    impl = impl or default_impl()
    if impl == "xla":
        return _bidir.bidir_binary_attention_xla(q, k, v)
    b, h, n, dk = q.shape
    nkv = k.shape[2]
    dv = v.shape[-1]
    qg = q.reshape(b * h, n, dk)
    kg = k.reshape(b * h, nkv, dk)
    vg = v.reshape(b * h, nkv, dv)
    # Lane-align head dims and sublane-align N; the kernel masks both.
    qp = _pad_to(_pad_to(qg, 128, 2), 8, 1)
    kp = _pad_to(_pad_to(kg, 128, 2), 8, 1)
    vp = _pad_to(_pad_to(vg, 128, 2), 8, 1)
    out = _bidir.bidir_binary_attention_pallas(
        qp, kp, vp, dk_true=dk, n_true=nkv, interpret=(impl == "interpret"))
    return out[:, :n, :dv].reshape(b, h, n, dv)


# ---------------------------------------------------------------------------
# fused causal binary linear attention
# ---------------------------------------------------------------------------

def binary_linear_attention_fused(q, k, v, *, chunk=None, impl=None,
                                  tune=None, return_state=False):
    """q,k: (B, H, N, Dk); v: (B, H, N, Dv). Causal, includes self.

    Inference/serving path (no VJP; training uses repro.core.add_attention).
    return_state=True additionally returns the final recurrent carry
    {"kv", "ksum", "vsum", "count"} (init_decode_state layout) so a chunked
    prefill can hand off directly to the O(1) decode step.
    """
    impl = impl or default_impl()
    b, h, n, dk = q.shape
    dv = v.shape[-1]
    if impl == "xla":
        out = _ref.binary_linear_attention_ref(q, k, v, causal=True)
        if not return_state:
            return out
        return out, _ref.binary_linear_attention_state_ref(q, k, v)
    if chunk is None:
        # Explicit chunk > tuned VMEM-residency chunk > module default.
        cfg = _tuned(tune, "linear_attention", g=b * h, n=n, dk=dk, dv=dv) or {}
        chunk = min(cfg.get("chunk", _linattn.CHUNK), n)
    qg = q.reshape(b * h, n, dk)
    kg = k.reshape(b * h, n, dk)
    vg = v.reshape(b * h, n, dv)
    # Lane-align head dims; the kernel masks the padded lanes (dk_true).
    qp = _pad_to(qg, 128, 2)
    kp = _pad_to(kg, 128, 2)
    vp = _pad_to(vg, 128, 2)
    pad_n = (-n) % chunk
    if pad_n:
        qp = _pad_to(qp, chunk, 1)
        kp = _pad_to(kp, chunk, 1)
        vp = _pad_to(vp, chunk, 1)
    res = _linattn.binary_linear_attention_pallas(
        qp, kp, vp, dk_true=dk, chunk=chunk, n_true=n,
        interpret=(impl == "interpret"), return_state=return_state)
    if not return_state:
        return res[:, :n, :dv].reshape(b, h, n, dv)
    out, kv, ksum, vsum = res
    state = {
        "kv": kv[:, :dk, :dv].reshape(b, h, dk, dv),
        "ksum": ksum[:, 0, :dk].reshape(b, h, dk),
        "vsum": vsum[:, 0, :dv].reshape(b, h, dv),
        "count": jnp.asarray(float(n), jnp.float32),
    }
    return out[:, :n, :dv].reshape(b, h, n, dv), state
