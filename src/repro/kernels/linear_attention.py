"""Pallas TPU kernel: fused chunked causal binary linear attention.

The paper's stage-1 attention (linear order Q(KᵀV) + binary Q/K codes) as one
fused kernel. The O(n) rewrite leaves a (d_k × d_v) running state; the fusion
keeps that state **resident in VMEM across the whole sequence** — HBM sees
each q/k/v chunk exactly once and each output chunk exactly once. This is the
TPU-native version of what the paper's TVM kernels buy on GPU: the win is
data movement, not multiplier counts.

Per (batch*head) g and chunk i (grid (G, N/C), chunk axis sequential):

    bq, bk    = sign(q_i), sign(k_i)                 (binarize fused, ±1)
    num       = bq @ KV  + d * 1·vsum                (inter-chunk, state)
    den       = bq @ ksum + d * (i*C)
    S         = tril(bq @ bkᵀ + d)                   (intra-chunk causal)
    out_i     = (num + S @ v_i) / (den + rowsum(S))
    KV       += bkᵀ @ v_i;  ksum += Σbk;  vsum += Σv (state update)

Head dims are zero-masked up to the true d_k/d_v so the wrapper may pad to
lane alignment without changing the Hamming kernel's `+d` offsets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


CHUNK = 256


def _make_kernel(dk_true: int, chunk: int, n_true: int, return_state: bool):
    def kernel(q_ref, k_ref, v_ref, o_ref, *rest):
        if return_state:
            kv_out, ksum_out, vsum_out, kv_ref, ksum_ref, vsum_ref = rest
        else:
            kv_ref, ksum_ref, vsum_ref = rest
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            kv_ref[...] = jnp.zeros_like(kv_ref)
            ksum_ref[...] = jnp.zeros_like(ksum_ref)
            vsum_ref[...] = jnp.zeros_like(vsum_ref)

        q = q_ref[0].astype(jnp.float32)              # (C, dk_pad)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)              # (C, dv_pad)
        dk_pad = q.shape[-1]
        dv_pad = v.shape[-1]
        # Binarize; zero the padded feature lanes so they drop out of dots.
        lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, dk_pad), 1)
        valid = (lane < dk_true).astype(jnp.float32)
        bq = jnp.where(q >= 0, 1.0, -1.0) * valid
        bk = jnp.where(k >= 0, 1.0, -1.0) * valid
        # Zero padded sequence positions (tail chunk): their k/v must not
        # enter the carry, and causal masking keeps them out of real outputs.
        row_k = jax.lax.broadcasted_iota(jnp.int32, (chunk, dk_pad), 0)
        bk = bk * (i * chunk + row_k < n_true).astype(jnp.float32)
        row_v = jax.lax.broadcasted_iota(jnp.int32, (chunk, dv_pad), 0)
        v = v * (i * chunk + row_v < n_true).astype(jnp.float32)

        d = jnp.float32(dk_true)
        cnt_prev = (i * chunk).astype(jnp.float32)
        # Inter-chunk terms from the running state.
        num = jnp.dot(bq, kv_ref[...], preferred_element_type=jnp.float32)
        num += d * vsum_ref[...]                      # (1, dv) broadcasts
        den = jnp.sum(bq * ksum_ref[...], axis=-1) + d * cnt_prev  # (C,)
        # Intra-chunk causal term.
        s = jnp.dot(bq, bk.T, preferred_element_type=jnp.float32) + d
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        s = jnp.where(col <= row, s, 0.0)
        num += jnp.dot(s, v, preferred_element_type=jnp.float32)
        den += jnp.sum(s, axis=-1)
        o_ref[0] = (num / (den[:, None] + 1e-6)).astype(o_ref.dtype)
        # State update (after emitting this chunk's outputs).
        kv_ref[...] += jnp.dot(bk.T, v, preferred_element_type=jnp.float32)
        ksum_ref[...] += jnp.sum(bk, axis=0, keepdims=True)
        vsum_ref[...] += jnp.sum(v, axis=0, keepdims=True)
        if return_state:
            # Same (gg, 0) block every chunk step; the last write survives —
            # the final carry leaves VMEM exactly once per (batch*head).
            kv_out[0] = kv_ref[...]
            ksum_out[0] = ksum_ref[...]
            vsum_out[0] = vsum_ref[...]

    return kernel


@functools.partial(jax.jit, static_argnames=("dk_true", "chunk", "n_true",
                                              "interpret", "return_state"))
def binary_linear_attention_pallas(q, k, v, *, dk_true=None, chunk=CHUNK,
                                   n_true=None, interpret=False,
                                   return_state=False):
    """q,k: (G, N, Dk); v: (G, N, Dv); causal, includes self. N % chunk == 0.

    dk_true: the unpadded head dim (defaults to Dk) — see module docstring.
    n_true: the unpadded sequence length (defaults to N); positions beyond it
      are masked out of the carry so the wrapper may pad N to a chunk multiple.
    return_state: additionally emit the final recurrent carry
      (kv (G, Dk, Dv), ksum (G, 1, Dk), vsum (G, 1, Dv)) — the parallel-prefill
      handoff into the O(1) decode state.
    """
    g, n, dk = q.shape
    dv = v.shape[-1]
    assert n % chunk == 0, (n, chunk)
    dk_true = dk if dk_true is None else int(dk_true)
    n_true = n if n_true is None else int(n_true)
    grid = (g, n // chunk)
    out_specs = pl.BlockSpec((1, chunk, dv), lambda gg, i: (gg, i, 0))
    out_shape = jax.ShapeDtypeStruct((g, n, dv), v.dtype)
    if return_state:
        out_specs = [
            out_specs,
            pl.BlockSpec((1, dk, dv), lambda gg, i: (gg, 0, 0)),
            # (G, 1, D) so each block's last two dims equal the array's —
            # a (1, D) block over (G, D) breaks the TPU (8, 128) tiling rule.
            pl.BlockSpec((1, 1, dk), lambda gg, i: (gg, 0, 0)),
            pl.BlockSpec((1, 1, dv), lambda gg, i: (gg, 0, 0)),
        ]
        out_shape = [
            out_shape,
            jax.ShapeDtypeStruct((g, dk, dv), jnp.float32),
            jax.ShapeDtypeStruct((g, 1, dk), jnp.float32),
            jax.ShapeDtypeStruct((g, 1, dv), jnp.float32),
        ]
    return pl.pallas_call(
        _make_kernel(dk_true, chunk, n_true, return_state),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda gg, i: (gg, i, 0)),
            pl.BlockSpec((1, chunk, dk), lambda gg, i: (gg, i, 0)),
            pl.BlockSpec((1, chunk, dv), lambda gg, i: (gg, i, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),
            pltpu.VMEM((1, dk), jnp.float32),
            pltpu.VMEM((1, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
