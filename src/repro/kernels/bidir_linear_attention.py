"""Pallas TPU kernel: fused bidirectional (encoder) binary linear attention.

The ViT serving form of the paper's Hamming-kernel attention. The causal
kernel (linear_attention.py) must scan chunks to respect the mask; the
encoder form has no mask, so the whole computation collapses into ONE fused
pass per (batch*head):

    bq, bk = sign(q), sign(k)                  (binarize fused in VMEM, ±1)
    KV     = bkᵀ @ v          ksum = Σ bk       vsum = Σ v
    out    = (bq @ KV + d·vsum) / (bq·ksum + d·n)

`core/add_attention._bidirectional` runs this as four separate full-precision
einsums through the STE machinery — each materializing its operands in HBM.
Here the codes never leave VMEM: HBM sees q/k/v once and out once, which is
the whole win (the contractions are ±1 adds; the paper's speedup is data
movement, not multiplier counts — same argument as the causal kernel).

Also hosts the XLA inference twin (`bidir_binary_attention_xla`): no STE
(inference has no gradient, so the straight-through machinery is dead
weight), and every ±1 contraction is done via the sign trick — with
m = 1[x ≥ 0] ∈ {0,1} and b = 2m − 1,

    b @ Y = 2·(m @ Y) − colsum(Y)

i.e. a masked add (popcount-style: accumulate only the rows the mask keeps)
plus a shared column sum, never materializing the ±1 codes.

Head dims are zero-masked up to the true d_k/d_v and sequence rows up to the
true n, so the ops.py wrapper may pad to lane/sublane alignment without
changing the Hamming kernel's `+d` offsets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


# Upper bound on padded sequence length: q, k, v, codes and out all live in
# VMEM simultaneously (~6 · N · 128 lanes · 4 B ≈ 12 MB at N=4096). Longer
# encoder sequences should go through the chunked causal kernel's dataflow.
MAX_FUSED_N = 4096


def _make_kernel(dk_true: int, n_true: int):
    def kernel(q_ref, k_ref, v_ref, o_ref):
        q = q_ref[0].astype(jnp.float32)              # (Np, dk_pad)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)              # (Np, dv_pad)
        n_pad, dk_pad = q.shape
        dv_pad = v.shape[-1]
        # Binarize; zero the padded feature lanes so they drop out of dots.
        lane = jax.lax.broadcasted_iota(jnp.int32, (n_pad, dk_pad), 1)
        lane_valid = (lane < dk_true).astype(jnp.float32)
        bq = jnp.where(q >= 0, 1.0, -1.0) * lane_valid
        bk = jnp.where(k >= 0, 1.0, -1.0) * lane_valid
        # Zero padded sequence rows: their k/v must not enter the global sums
        # (padded *query* rows produce garbage rows sliced off outside).
        row_k = jax.lax.broadcasted_iota(jnp.int32, (n_pad, dk_pad), 0)
        bk = bk * (row_k < n_true).astype(jnp.float32)
        row_v = jax.lax.broadcasted_iota(jnp.int32, (n_pad, dv_pad), 0)
        v = v * (row_v < n_true).astype(jnp.float32)

        d = jnp.float32(dk_true)
        # Phase 1: global accumulators (codes stay resident in VMEM).
        kv = jnp.dot(bk.T, v, preferred_element_type=jnp.float32)   # (dk, dv)
        ksum = jnp.sum(bk, axis=0, keepdims=True)                   # (1, dk)
        vsum = jnp.sum(v, axis=0, keepdims=True)                    # (1, dv)
        # Phase 2: emit every output row against the finished accumulators.
        num = jnp.dot(bq, kv, preferred_element_type=jnp.float32)
        num += d * vsum                                             # broadcasts
        den = jnp.sum(bq * ksum, axis=-1) + d * jnp.float32(n_true)  # (Np,)
        o_ref[0] = (num / (den[:, None] + 1e-6)).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("dk_true", "n_true", "interpret"))
def bidir_binary_attention_pallas(q, k, v, *, dk_true=None, n_true=None,
                                  interpret=False):
    """q, k: (G, N, Dk); v: (G, N, Dv) → (G, N, Dv). Non-causal.

    dk_true / n_true: the unpadded head dim / sequence length (default Dk/N);
    padded lanes and rows are masked out of the Hamming kernel inside VMEM so
    the wrapper may pad to tile alignment freely.
    """
    g, n, dk = q.shape
    dv = v.shape[-1]
    dk_true = dk if dk_true is None else int(dk_true)
    n_true = n if n_true is None else int(n_true)
    assert n <= MAX_FUSED_N, (
        f"fused bidirectional kernel holds the whole sequence in VMEM; "
        f"N={n} > {MAX_FUSED_N} — use the chunked causal kernel dataflow")
    return pl.pallas_call(
        _make_kernel(dk_true, n_true),
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, n, dk), lambda gg: (gg, 0, 0)),
            pl.BlockSpec((1, n, dk), lambda gg: (gg, 0, 0)),
            pl.BlockSpec((1, n, dv), lambda gg: (gg, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n, dv), lambda gg: (gg, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, n, dv), v.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(q, k, v)


def bidir_binary_attention_xla(q, k, v):
    """XLA inference twin of the fused kernel. q, k: (B, H, N, Dk); v: (B, H,
    N, Dv) → (B, H, N, Dv).

    No STE (nothing differentiates through serving), and the ±1 contractions
    use the sign trick (module docstring): the {0,1} masks are the only
    "codes" ever materialized, each contraction is a masked add, and the
    correction terms (colsum(KV), Σksum) are O(d·d) / O(d) — free next to the
    O(n·d²) contractions they replace.
    """
    out_dtype = v.dtype
    d = q.shape[-1]
    n = q.shape[-2]
    v32 = v.astype(jnp.float32)
    mq = (q >= 0).astype(jnp.float32)
    mk = (k >= 0).astype(jnp.float32)
    vsum = jnp.sum(v32, axis=-2)                                  # (B,H,Dv)
    # KV = bkᵀ v = 2·(mkᵀ v) − 1·vsum ; ksum = Σbk = 2·Σmk − n
    kv = 2.0 * jnp.einsum("bhnd,bhne->bhde", mk, v32) - vsum[:, :, None, :]
    ksum = 2.0 * jnp.sum(mk, axis=-2) - jnp.float32(n)            # (B,H,Dk)
    # bq @ KV = 2·(mq @ KV) − colsum(KV) ; bq·ksum = 2·(mq·ksum) − Σksum
    num = (2.0 * jnp.einsum("bhnd,bhde->bhne", mq, kv)
           - jnp.sum(kv, axis=-2)[:, :, None, :]
           + d * vsum[:, :, None, :])
    den = (2.0 * jnp.einsum("bhnd,bhd->bhn", mq, ksum)
           - jnp.sum(ksum, axis=-1)[..., None]
           + jnp.float32(d * n))
    return (num / (den[..., None] + 1e-6)).astype(out_dtype)
