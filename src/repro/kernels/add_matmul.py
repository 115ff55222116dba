"""Pallas TPU kernel: MatAdd — batched y = x @ b with b ∈ {-1, 0, +1} int8.

The paper's Add layer: a MatMul whose second operand is binarized, so every
MAC degenerates to an accumulation. On TPU the win is operand bytes: b is
stored int8 (1 B/element; a bit-packed 1-bit variant is the beyond-paper
extension, see ops.add_matmul_bitpacked) and expanded to bf16 only inside
VMEM, feeding the MXU.

Used for the attention contractions Q(KᵀV) where K (and Q) are binary codes;
hence the batched (G = B*H) layout.

Grid: (G, M/bm, N/bn, K/bk), K innermost with an fp32 VMEM accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu
from repro.kernels.padding import pad_to_multiple as _pad_axis


BM, BN, BK = 128, 128, 512


def _add_matmul_kernel(x_ref, b_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ±1/0 int8 → bf16 is exact; the "multiply" by ±1 is sign-propagation.
    b = b_ref[0].astype(jnp.bfloat16)
    acc_ref[...] += jnp.dot(
        x_ref[0].astype(jnp.bfloat16), b, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def add_matmul_pallas(x, b, *, bm=BM, bn=BN, bk=BK, interpret=False):
    """x: (G, M, K) float; b: (G, K, N) int8. Returns (G, M, N) in x.dtype.

    Shapes need NOT be multiples of the block sizes: inputs are zero-padded
    up to the tile grid and the output sliced back — real ViT token counts
    (197 for DeiT, 197-patch buckets) are first-class. Zero padding is exact
    for this contraction (0 · ±1 = 0).
    """
    g, m, k = x.shape
    g2, k2, n = b.shape
    assert g == g2 and k == k2, (x.shape, b.shape)
    x = _pad_axis(_pad_axis(x, bm, 1), bk, 2)
    b = _pad_axis(_pad_axis(b, bk, 1), bn, 2)
    (_, mp, kp), np_ = x.shape, b.shape[2]
    grid = (g, mp // bm, np_ // bn, kp // bk)
    y = pl.pallas_call(
        _add_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda gg, i, j, kk: (gg, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda gg, i, j, kk: (gg, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda gg, i, j, kk: (gg, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, b)
    return y[:, :m, :n]
