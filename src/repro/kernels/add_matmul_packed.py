"""Pallas TPU kernel: bit-packed MatAdd — y = x @ b with b stored 1 BIT/element.

Beyond-paper extension of the Add layer (the paper stores binarized operands
as int8 = 8 bits/element): the ±1 codes are packed 8-per-byte along the
contraction dim, cutting the binary operand's HBM traffic a further 8×
(16× vs bf16). The kernel unpacks inside VMEM with integer shifts and feeds
the MXU — same dataflow as add_matmul, different storage format.

Packing: packed[g, k8, n] bit j  ⇔  b[g, k8*8 + j, n] > 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu
from repro.kernels.padding import pad_to_multiple as _pad_axis


BM, BN, BK8 = 128, 128, 64          # BK8 packed rows = 512 logical K rows


def pack_bits(b):
    """b: (G, K, N) in {-1,+1} (int8/float) → (G, K//8, N) uint8."""
    g, k, n = b.shape
    assert k % 8 == 0, k
    bits = (b > 0).astype(jnp.uint8).reshape(g, k // 8, 8, n)
    weights = (1 << jnp.arange(8, dtype=jnp.uint8))[None, None, :, None]
    return jnp.sum(bits * weights, axis=2).astype(jnp.uint8)


def unpack_bits(packed, dtype=jnp.float32):
    """(G, K8, N) uint8 → (G, K8*8, N) ±1 in `dtype` (reference path)."""
    g, k8, n = packed.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)[None, None, :, None]
    bits = (packed[:, :, None, :] >> shifts) & 1
    return (bits.astype(dtype) * 2.0 - 1.0).reshape(g, k8 * 8, n)


def _kernel(x_ref, p_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Unpack in int32 and go through f32 to bf16: Mosaic lowers neither
    # 8-bit vector shifts nor a direct uint8 → bf16 cast.
    p = p_ref[0].astype(jnp.int32)                    # (BK8, BN), 0..255
    k8, bn = p.shape
    shifts = jax.lax.broadcasted_iota(jnp.int32, (k8, 8, bn), 1)
    bits = (p[:, None, :] >> shifts) & 1
    b = (bits.astype(jnp.float32) * 2.0 - 1.0).reshape(k8 * 8, bn)
    b = b.astype(jnp.bfloat16)
    acc_ref[...] += jnp.dot(x_ref[0].astype(jnp.bfloat16), b,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk8", "interpret"))
def add_matmul_packed_pallas(x, packed, *, bm=BM, bn=BN, bk8=BK8,
                             interpret=False):
    """x: (G, M, K); packed: (G, K//8, N) uint8 → (G, M, N).

    M/N/K8 need not be block multiples: inputs are padded to the tile grid
    and the output sliced. Padded packed bytes decode to -1 rows, but x is
    zero-padded over the same logical K rows, so they contribute nothing.
    """
    g, m, k = x.shape
    g2, k8, n = packed.shape
    assert g == g2 and k == k8 * 8, (x.shape, packed.shape)
    x = _pad_axis(_pad_axis(x, bm, 1), bk8 * 8, 2)
    packed = _pad_axis(_pad_axis(packed, bk8, 1), bn, 2)
    (_, mp, _), (k8p, np_) = x.shape, packed.shape[1:]
    grid = (g, mp // bm, np_ // bn, k8p // bk8)
    y = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk8 * 8), lambda gg, i, j, kk: (gg, i, kk)),
            pl.BlockSpec((1, bk8, bn), lambda gg, i, j, kk: (gg, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda gg, i, j, kk: (gg, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, packed)
    return y[:, :m, :n]
