"""Pallas TPU kernel: MatShift — y = x @ (s * 2^P) with packed int8 weights.

TPU adaptation of the paper's TVM MatShift (DESIGN.md §2). The paper's own
profiling says the GPU speedup is "almost fully hidden behind data movements";
on TPU we realize exactly that saving: weights live in HBM as **1 packed byte
per weight** (bit 7 = sign, bits 0-6 = P+64), halving weight traffic vs bf16.
Inside VMEM the bf16 power-of-two value is assembled with three integer ops
and a bitcast — the MXU then runs the contraction at full rate:

    bf16(s * 2^P)  =  bitcast( sign << 15  |  (P + 127) << 7 )

Grid: (M/bm, N/bn, K/bk); fp32 accumulator scratch in VMEM, K innermost
("arbitrary" semantics) so the accumulator carries across K steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu
from repro.kernels.padding import pad_to_multiple as _pad_axis


from repro.core.quant import P_MIN

# MXU-aligned default tiling: (128, 128) output tile, 512-deep K panel.
BM, BN, BK = 128, 128, 512


def _assemble_bf16(sp):
    """packed int8 (sign|P+64) → exact bf16 s*2^P, integer ops only.

    The decode runs in 32-bit integers: Mosaic cannot lower shifts of 8- or
    16-bit vectors. The bits are assembled as an f32 (same sign and exponent
    layout as bf16, zero mantissa), so narrowing to bf16 is exact."""
    u = sp.astype(jnp.int32) & 0xFF                   # sign-extend, re-mask
    sign = (u >> 7) << 31
    p = (u & 0x7F) + P_MIN                            # P in [-64, 63]
    exp_field = (p + 127) << 23                       # f32 exponent, mantissa 0
    w = jax.lax.bitcast_convert_type(sign | exp_field, jnp.float32)
    return w.astype(jnp.bfloat16)


def _shift_matmul_kernel(x_ref, sp_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _assemble_bf16(sp_ref[...])
    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.bfloat16), w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def shift_matmul_pallas(x, w_packed, *, bm=BM, bn=BN, bk=BK, interpret=False):
    """x: (M, K) float; w_packed: (K, N) int8. Returns (M, N) in x.dtype.

    Shapes need NOT be multiples of the block sizes: inputs are zero-padded
    to the tile grid and the output sliced back. A padded packed byte decodes
    to a tiny-but-nonzero power of two, which is harmless: x is zero-padded
    over the same K rows, so every padded term is w · 0 = 0 and the sum is
    exact. Padded M rows / N columns are discarded by the slice.
    """
    m, k = x.shape
    k2, n = w_packed.shape
    assert k == k2, (x.shape, w_packed.shape)
    x = _pad_axis(_pad_axis(x, bm, 0), bk, 1)
    w_packed = _pad_axis(_pad_axis(w_packed, bk, 0), bn, 1)
    (mp, kp), np_ = x.shape, w_packed.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)
    y = pl.pallas_call(
        _shift_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_packed)
    return y[:m, :n]
