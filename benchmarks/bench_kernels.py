"""Paper Fig. 4/5 (+ App. A): MatShift / MatAdd kernel comparison.

On-target (TPU) the win is data movement; this container is CPU-only, so we
report (a) measured CPU wall time of the semantics-equivalent XLA paths as a
sanity harness, and (b) the *derived* roofline-model speedup on v5e from the
operand-byte reduction (packed int8 weights / binary operands vs bf16), which
is the quantity the paper's GPU numbers correspond to.

Shapes follow the paper's Fig. 4/5 convention: inputs (B, K, M) weights (K, N)
for MatShift; (B, H, K, M) x (B, H, K, N) for MatAdd, dims w.r.t. PVT sizes.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core.energy import HBM_BW, PEAK_FLOPS_BF16, PEAK_OPS_INT8
from repro.kernels import ops


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def _roofline_time(flops, bytes_, int8=False):
    peak = PEAK_OPS_INT8 if int8 else PEAK_FLOPS_BF16
    return max(flops / peak, bytes_ / HBM_BW)


def bench_matshift(rows):
    # First three follow the paper's Fig. 4 PVT shapes (activation-dominated:
    # gains hide behind data movement exactly as the paper observes); the
    # last two are decode-regime weight-dominated shapes where the packed
    # int8 weights pay off directly.
    shapes = [(1, 512, 3136, 64), (1, 1024, 784, 128), (32, 512, 196, 320),
              (1, 4096, 64, 11008), (1, 8192, 16, 8192)]
    for b, k, m, n in shapes:
        x = jax.random.normal(jax.random.PRNGKey(0), (b * m, k), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n)) * 0.05
        wp = quant.pack_from_dense(w)
        wb = w.astype(jnp.bfloat16)
        t_dense = _time(jax.jit(lambda x, w: x @ w.astype(x.dtype)), x, wb)
        t_shift = _time(jax.jit(lambda x, wp: ops.shift_matmul(x, wp, "xla")), x, wp)
        flops = 2.0 * b * m * k * n
        bytes_dense = (b * m * k + k * n + b * m * n) * 2
        bytes_shift = b * m * k * 2 + k * n * 1 + b * m * n * 2
        derived = (_roofline_time(flops, bytes_dense)
                   / _roofline_time(flops, bytes_shift, int8=True))
        rows.append(("matshift_%dx%dx%dx%d" % (b, k, m, n), t_shift,
                     f"tpu_speedup_vs_dense={derived:.2f};cpu_dense_us={t_dense:.0f}"))


def bench_matadd_bitpacked(rows):
    """Beyond-paper: 1-bit packed binary operand (8× less than the paper's
    int8). Derived roofline gain shows where operand traffic dominates."""
    from repro.kernels.add_matmul_packed import pack_bits

    g, m, k, n = 8, 64, 4096, 4096        # decode-regime KV contraction
    b = (jax.random.randint(jax.random.PRNGKey(1), (g, k, n), 0, 2,
                            jnp.int8) * 2 - 1).astype(jnp.int8)
    packed = pack_bits(b)
    x = jax.random.normal(jax.random.PRNGKey(0), (g, m, k))
    t = _time(jax.jit(lambda x, p: ops.add_matmul_bitpacked(x, p, "xla")),
              x, packed, iters=2)
    flops = 2.0 * g * m * k * n
    bytes_int8 = g * (m * k * 2 + k * n * 1 + m * n * 2)
    bytes_bit = g * (m * k * 2 + k * n / 8 + m * n * 2)
    derived = (_roofline_time(flops, bytes_int8, int8=True)
               / _roofline_time(flops, bytes_bit, int8=True))
    rows.append((f"matadd_bitpacked_{g}x{m}x{k}x{n}", t,
                 f"tpu_speedup_vs_int8_operand={derived:.2f}"))


def bench_matadd(rows):
    shapes = [(1, 8, 64, 3136, 64), (1, 8, 64, 784, 784)]
    for b, h, k, m, n in shapes:
        x = jax.random.normal(jax.random.PRNGKey(0), (b * h, m, k))
        bq = (jax.random.randint(jax.random.PRNGKey(1), (b * h, k, n), 0, 2,
                                 jnp.int8) * 2 - 1).astype(jnp.int8)
        bf = bq.astype(jnp.float32)
        t_dense = _time(jax.jit(lambda x, b: jnp.einsum("gmk,gkn->gmn", x, b)), x, bf)
        t_add = _time(jax.jit(lambda x, b: ops.add_matmul(x, b, "xla")), x, bq)
        flops = 2.0 * b * h * m * k * n
        bytes_dense = (b * h) * (m * k + k * n + m * n) * 2
        bytes_add = (b * h) * (m * k * 2 + k * n * 1 + m * n * 2)
        derived = (_roofline_time(flops, bytes_dense)
                   / _roofline_time(flops, bytes_add, int8=True))
        rows.append(("matadd_%dx%dx%dx%dx%d" % (b, h, k, m, n), t_add,
                     f"tpu_speedup_vs_dense={derived:.2f};cpu_dense_us={t_dense:.0f}"))


def main(rows=None):
    own = rows is None
    rows = [] if own else rows
    bench_matshift(rows)
    bench_matadd(rows)
    bench_matadd_bitpacked(rows)
    if own:
        for r in rows:
            print(",".join(str(c) for c in r))
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
