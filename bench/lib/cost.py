"""Operations and bytes of one call of each kernel, counted from the
algorithm, and the roofline they set. A family's `cost.py` gives the calls
of its model; these give what each call costs.

One multiply-add is two operations. A shift or binary matmul counts as many
operations as the dense matmul of its shape.
"""
from __future__ import annotations


def shift_matmul_cost(m: int, k: int, n: int) -> tuple:
    """(ops, bytes) of y (m, n) f32 = x (m, k) f32 @ w (k, n), w one int8
    byte per weight: every operand read or written once."""
    return 2.0 * m * k * n, 4.0 * m * k + 1.0 * k * n + 4.0 * m * n


def bidir_attn_cost(g: int, n: int, dk: int, dv: int) -> tuple:
    """(ops, bytes) of fused binary linear attention over g (batch x head)
    groups: k^T v and q (k^T v) at two operations a term, the row sums and
    the normaliser; q, k, v read and the output written once, in f32."""
    ops = g * (2.0 * n * dk * dv * 2 + 2.0 * n * dk + 2.0 * n * dv)
    return ops, 4.0 * g * n * (2 * dk + 2 * dv)


def roofline_s(ops: float, nbytes: float, peak_ops: float,
               peak_bytes_per_s: float) -> float:
    """Least time the chip could take: the larger of the compute bound and
    the memory bound."""
    return max(ops / peak_ops, nbytes / peak_bytes_per_s)
